"""Compiled-executor tests: plan cache, static wait plans, and
compiled-vs-interpreted parity (results AND per-rank tag consumption)
across algorithms × modes × world/split/cart communicators — including
the rejected-call path and mixed-executor ranks sharing one wire."""

import numpy as np
import pytest

from repro.core import (Collectives, HaloExchange, HierarchicalCollectives,
                        TaskRuntime, tac)
from repro.core import program as program_ir
from repro.core import schedule as schedule_ir
from repro.core.collectives import (CollectiveHandle, _drive_group,
                                    _Machine)
from repro.core.schedule import Recv

EXECUTORS = ("interpreted", "compiled")
COLLS = ("barrier", "bcast", "reduce", "allreduce", "allgather",
         "reduce_scatter", "alltoall")


@pytest.fixture(autouse=True)
def _task_multiple():
    tac.init(tac.TASK_MULTIPLE)
    yield
    tac.init(tac.TASK_MULTIPLE)


def _same(a, b):
    """Structural equality over the collectives' result shapes."""
    if type(a) is not type(b) and not (
            isinstance(a, np.ndarray) and isinstance(b, np.ndarray)):
        return False
    if isinstance(a, np.ndarray):
        return a.shape == b.shape and np.array_equal(a, b)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_same, a, b))
    if isinstance(a, dict):
        return set(a) == set(b) and all(_same(a[k], b[k]) for k in a)
    return a == b


def _seq_state(coll):
    """Observable per-rank tag-sequence positions (count(k) reprs)."""
    return [repr(c) for c in coll._seq]


def _per_rank_kwargs(name, m, vals, root):
    per = []
    for r in range(m):
        if name == "barrier":
            per.append({})
        elif name == "bcast":
            per.append({"value": vals[r] if r == root else None,
                        "root": root})
        elif name == "reduce":
            per.append({"value": vals[r], "root": root})
        elif name == "alltoall":
            per.append({"blocks": [vals[r] + d for d in range(m)]})
        else:
            per.append({"value": vals[r]})
    return per


def _run_both(name, n, algorithm, make_comm, per_rank, **common):
    """The same collective on two fresh communicators, one per executor;
    returns {executor: (results, seq_state)}."""
    out = {}
    for ex in EXECUTORS:
        comm = make_comm()
        coll = Collectives(comm, executor=ex)
        res = coll.run_group(name, per_rank, algorithm=algorithm, **common)
        out[ex] = (res, _seq_state(coll))
    return out


# ---------------------------------------------------------------------------
# exhaustive small-matrix parity (no hypothesis needed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", COLLS)
@pytest.mark.parametrize("algorithm", [None, "ring", "doubling", "auto"])
@pytest.mark.parametrize("kind", ["world", "split", "cart"])
def test_parity_across_collectives_algorithms_comms(name, algorithm, kind):
    n = 6
    if kind == "world":
        m, make_comm = n, lambda: tac.CommWorld(n)
    elif kind == "split":
        m = 3

        def make_comm():
            w = tac.CommWorld(n)
            handles = [w.split(r // 3, key=r, rank=r) for r in range(n)]
            return handles[0].result      # color-0 group, ranks 0..2
    else:
        m = 4

        def make_comm():
            return tac.CommWorld(n).cart_create((2, 2), periodic=True)
    vals = [np.arange(4.0) * (r + 1) for r in range(m)]
    per = _per_rank_kwargs(name, m, vals, root=m - 1)
    out = _run_both(name, m, algorithm, make_comm, per)
    (res_i, seq_i), (res_c, seq_c) = out["interpreted"], out["compiled"]
    assert _same(res_i, res_c)
    assert seq_i == seq_c


@pytest.mark.parametrize("segments", [1, 3])
def test_parity_segmented_and_hierarchical_allreduce(segments):
    n = 8
    vals = [np.arange(16.0) + r for r in range(n)]
    per = [{"value": v} for v in vals]
    out = _run_both("allreduce", n, "ring", lambda: tac.CommWorld(n), per,
                    segments=segments)
    assert _same(out["interpreted"][0], out["compiled"][0])
    assert out["interpreted"][1] == out["compiled"][1]

    out = _run_both("allreduce", n, None, lambda: tac.CommWorld(n), per,
                    hierarchical=4)
    assert _same(out["interpreted"][0], out["compiled"][0])

    res = {}
    for ex in EXECUTORS:
        hier = HierarchicalCollectives(tac.CommWorld(n), 4, executor=ex)
        res[ex] = (hier.run_group(vals),
                   hier.run_group(vals, composed=True))
    assert _same(res["interpreted"], res["compiled"])


def test_parity_halo_and_persistent():
    outs = {}
    for ex in EXECUTORS:
        cart = tac.CommWorld(6).cart_create((2, 3), periodic=(True, False))
        halo = HaloExchange(cart, executor=ex)
        sends = [{d: (r, d) for d in dict(halo.neighbors(r))}
                 for r in range(6)]
        outs[ex] = [halo.run_group(sends) for _ in range(3)]
    assert _same(outs["interpreted"], outs["compiled"])

    outs = {}
    vals = [np.arange(5.0) + r for r in range(4)]
    for ex in EXECUTORS:
        coll = Collectives(tac.CommWorld(4), executor=ex)
        pers = coll.persistent("allreduce", algorithm="doubling", op="max")
        outs[ex] = [pers.run_group(vals) for _ in range(3)]
    assert _same(outs["interpreted"], outs["compiled"])


# ---------------------------------------------------------------------------
# interoperability modes inside a runtime
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["blocking", "event"])
def test_mode_parity_inside_tasks(mode):
    n = 4
    vals = [np.full(6, float(r + 1)) for r in range(n)]
    ref = np.sum(np.stack(vals), axis=0)
    for ex in EXECUTORS:
        coll = Collectives(tac.CommWorld(n), executor=ex)
        got = {}

        def comm(r):
            def body():
                got[r] = coll.allreduce(vals[r], rank=r, op="sum",
                                        mode=mode, key="m")
            return body

        with TaskRuntime(num_workers=2) as rt:
            for r in range(n):
                rt.submit(comm(r), out=[("res", r)])
            rt.taskwait()
        for r in range(n):
            res = got[r].result if mode == "event" else got[r]
            np.testing.assert_allclose(res, ref)


def test_mixed_executor_ranks_share_the_wire():
    """Compiled and interpreted ranks of ONE collective on the SAME
    communicator: byte-identical tags mean they match and agree."""
    n = 8
    w = tac.CommWorld(n)
    colls = {ex: Collectives(w, executor=ex) for ex in EXECUTORS}
    for name, mk in [
            ("allreduce", lambda r: {"value": np.arange(4.0) + r}),
            ("bcast", lambda r: {"value": "x" if r == 0 else None}),
            ("allgather", lambda r: {"value": r}),
            ("alltoall", lambda r: {"blocks": [(r, d) for d in range(n)]}),
    ]:
        machines = []
        for r in range(n):
            ex = "compiled" if r % 2 else "interpreted"
            gen = colls[ex]._make_gen(name, rank=r, key=("mix", name),
                                      **mk(r))
            machines.append(_Machine(gen, CollectiveHandle()))
        _drive_group(machines)
        results = [m.handle.result for m in machines]
        ref_coll = Collectives(tac.CommWorld(n), executor="interpreted")
        ref = ref_coll.run_group(name, [mk(r) for r in range(n)])
        assert _same(results, ref)


# ---------------------------------------------------------------------------
# the hypothesis property: random collectives, communicators, payloads and
# rejected-call prefixes — results and tag consumption always agree
# ---------------------------------------------------------------------------
def test_parity_property_randomized():
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, HealthCheck
    import hypothesis.strategies as st

    @settings(deadline=None, max_examples=40,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def prop(data):
        n = data.draw(st.integers(2, 8), label="world size")
        kind = data.draw(st.sampled_from(["world", "split", "cart"]),
                         label="communicator kind")
        if kind == "world":
            m, make_comm = n, lambda: tac.CommWorld(n)
        elif kind == "split":
            k = data.draw(st.integers(1, n), label="split group size")
            m = min(k, n)

            def make_comm():
                w = tac.CommWorld(n)
                hs = [w.split(r // k, key=r, rank=r) for r in range(n)]
                return hs[0].result
        else:
            dims = data.draw(st.sampled_from(
                [(a, b) for a in (1, 2, 3) for b in (1, 2, 3)
                 if 2 <= a * b <= n]), label="cart dims")
            m = dims[0] * dims[1]

            def make_comm():
                return tac.CommWorld(n).cart_create(dims, periodic=True)
        name = data.draw(st.sampled_from(COLLS), label="collective")
        algorithm = data.draw(
            st.sampled_from([None, "ring", "doubling", "auto"]),
            label="algorithm")
        op = data.draw(st.sampled_from(["sum", "max", "min"]), label="op")
        length = data.draw(st.integers(1, 5), label="payload length")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        reject_first = data.draw(st.booleans(), label="rejected prefix")
        rng = np.random.default_rng(seed)
        vals = [rng.integers(-9, 9, size=length).astype(float)
                for _ in range(m)]
        root = data.draw(st.integers(0, m - 1), label="root")
        per = _per_rank_kwargs(name, m, vals, root)
        common = ({"op": op} if name in ("reduce", "allreduce",
                                         "reduce_scatter") else {})
        out = {}
        for ex in EXECUTORS:
            comm = make_comm()
            coll = Collectives(comm, executor=ex)
            if reject_first:
                # a rejected call on every rank must consume nothing
                for r in range(m):
                    with pytest.raises(ValueError):
                        coll.allreduce(vals[0], rank=r, mode="bogus")
            res = coll.run_group(name, per, algorithm=algorithm, **common)
            out[ex] = (res, _seq_state(coll))
        assert _same(out["interpreted"][0], out["compiled"][0])
        assert out["interpreted"][1] == out["compiled"][1]

    prop()


# ---------------------------------------------------------------------------
# rejected calls must not desynchronize tag sequences
# ---------------------------------------------------------------------------
def test_rejected_calls_never_consume_tag_sequence():
    n = 4
    vals = [np.arange(3.0) + r for r in range(n)]
    states = {}
    for ex in EXECUTORS:
        coll = Collectives(tac.CommWorld(n), executor=ex)
        coll.run_group("allreduce", [{"value": v} for v in vals])
        bad_calls = [
            lambda: coll.allreduce(vals[0], rank=0, mode="bogus"),
            lambda: coll.allreduce(vals[1], rank=1, algorithm="bogus"),
            lambda: coll.allreduce(vals[2], rank=2, op="bogus"),
            lambda: coll.allreduce(vals[0], rank=99),
            lambda: coll.run_group("allreduce",
                                   [{"value": v} for v in vals],
                                   segments=2, algorithm="doubling"),
            lambda: coll.run_group("allreduce",
                                   [{"value": v} for v in vals],
                                   hierarchical=3),
            lambda: coll.alltoall([1, 2], rank=0),
            lambda: coll.run_group("nope", [{}] * n),
        ]
        for bad in bad_calls:
            with pytest.raises(ValueError):
                bad()
        # every rank still in lockstep: the next keyless collective works
        res = coll.run_group("allreduce", [{"value": v} for v in vals])
        states[ex] = (_seq_state(coll), res)
    assert states["interpreted"][0] == states["compiled"][0]
    assert _same(states["interpreted"][1], states["compiled"][1])


def test_late_binding_errors_match_interpreter():
    """Binding failures surface on first advance (generator semantics) in
    both executors, not at gen-construction time."""
    for ex in EXECUTORS:
        coll = Collectives(tac.CommWorld(4), executor=ex)
        # too-few blocks reach binding only on first advance
        bad = coll._schedule("alltoall", None, 1, "k2", blocks=[1])
        with pytest.raises(IndexError):
            next(bad)


# ---------------------------------------------------------------------------
# the plan cache + static wait plans
# ---------------------------------------------------------------------------
def test_plan_cache_reuses_programs():
    program_ir.clear_cache()
    w = tac.CommWorld(4)
    coll = Collectives(w, executor="compiled")
    vals = [np.arange(3.0) + r for r in range(4)]
    coll.run_group("allreduce", [{"value": v} for v in vals])
    after_first = program_ir.cache_stats()
    assert after_first["misses"] >= 1
    for _ in range(5):
        coll.run_group("allreduce", [{"value": v} for v in vals])
    after = program_ir.cache_stats()
    assert after["misses"] == after_first["misses"]   # no recompiles
    assert after["hits"] > after_first["hits"]
    assert after["size"] == after_first["size"]

    # distinct op => distinct plan; same op string => shared entry
    coll.run_group("allreduce", [{"value": v} for v in vals], op="max")
    assert program_ir.cache_stats()["misses"] == after["misses"] + 1
    coll.run_group("allreduce", [{"value": v} for v in vals], op="max")
    assert program_ir.cache_stats()["misses"] == after["misses"] + 1


def test_plan_cache_eviction_bound(monkeypatch):
    program_ir.clear_cache()
    monkeypatch.setattr(program_ir, "CACHE_MAX", 2)
    w = tac.CommWorld(2)
    sched = schedule_ir.build("allreduce", "ring", 2)
    for i in range(5):
        program_ir.compile_schedule(sched, w, op=np.add, head=("t", i))
    stats = program_ir.cache_stats()
    assert stats["size"] <= 2
    assert stats["evictions"] == 3
    program_ir.clear_cache()
    assert program_ir.cache_stats()["size"] == 0


def test_compile_rejects_size_mismatch_and_missing_op():
    w = tac.CommWorld(4)
    sched = schedule_ir.build("allreduce", "ring", 3)
    with pytest.raises(ValueError, match="size"):
        program_ir.CompiledProgram(sched, w, op=np.add, head=("x",))
    prog = program_ir.CompiledProgram(
        schedule_ir.build("allreduce", "ring", 4), w, op=None, head=("x",))
    with pytest.raises(ValueError, match="no op"):
        prog.gen(0, 0, value=np.arange(3.0))
    with pytest.raises(ValueError, match="out of range"):
        prog.gen(7, 0, value=np.arange(3.0))


@pytest.mark.parametrize("name,algorithm", [
    ("allreduce", "ring"), ("allreduce", "doubling"),
    ("alltoall", "doubling"), ("allgather", "doubling"),
    ("reduce", "ring"), ("bcast", "doubling"), ("barrier", "doubling")])
def test_wait_plan_matches_dynamic_interpretation(name, algorithm):
    """The static wait plan equals what the interpreter's pending-dict
    probing computes dynamically, for every rank."""
    sched = schedule_ir.build(name, algorithm, 6)
    for rank in range(sched.n):
        steps, tail = sched.wait_plan(rank)
        assert len(steps) == len(sched.programs[rank])
        pending = {}
        for (op, waits), op2 in zip(steps, sched.programs[rank]):
            assert op is op2
            expect = tuple(b for b in op.reads if b in pending)
            assert waits == expect
            for b in waits:
                del pending[b]
            if isinstance(op, Recv):
                pending[op.buf] = None
        assert tail == tuple(pending)
        # every posted recv is consumed exactly once (waits ∪ tail)
        recvs = [op.buf for op in sched.programs[rank]
                 if isinstance(op, Recv)]
        consumed = [b for _, ws in steps for b in ws] + list(tail)
        assert sorted(map(repr, recvs)) == sorted(map(repr, consumed))


# ---------------------------------------------------------------------------
# persistent-plan buffer arenas: combine outputs write into pre-allocated
# per-rank buffers (ufunc out=), reused across postings of the same plan
# ---------------------------------------------------------------------------
def test_persistent_arena_reuses_combine_buffers():
    vals = [np.arange(16.0) + r for r in range(4)]
    want = np.sum(vals, axis=0)
    coll = Collectives(tac.CommWorld(4), executor="compiled")
    pers = coll.persistent("allreduce", algorithm="ring")
    for r in pers.run_group(vals):
        np.testing.assert_array_equal(r, want)
    assert any(pers._arenas), \
        "compiled ring allreduce should populate combine arenas"
    snap = [{k: id(v) for k, v in a.items()} for a in pers._arenas]
    for _ in range(10):
        for r in pers.run_group(vals):
            np.testing.assert_array_equal(r, want)
    # steady state: the very same buffer objects, and no growth (a new
    # entry or a reallocated id per iteration would be the leak).
    assert [{k: id(v) for k, v in a.items()} for a in pers._arenas] == snap


def test_persistent_arena_results_do_not_alias_buffers():
    n = 4
    vals1 = [np.full(12, float(r + 1)) for r in range(n)]
    vals2 = [np.full(12, float(10 * (r + 1))) for r in range(n)]
    for name in ("allreduce", "reduce"):
        coll = Collectives(tac.CommWorld(n), executor="compiled")
        pers = coll.persistent(name)
        out1 = pers.run_group(vals1)
        pers.run_group(vals2)
        # iteration 2 rewrote the arena buffers in place; iteration-1
        # results must be unaffected and share no memory with them.
        want1 = np.sum(vals1, axis=0)
        for res in out1:
            if res is None:        # reduce non-root
                continue
            np.testing.assert_array_equal(res, want1)
            for a in pers._arenas:
                for buf in a.values():
                    assert not np.shares_memory(res, buf)


def test_persistent_arena_survives_dtype_switch():
    coll = Collectives(tac.CommWorld(4), executor="compiled")
    pers = coll.persistent("allreduce", algorithm="ring")
    ints = [np.arange(8) + r for r in range(4)]
    flts = [np.arange(8.0) + r for r in range(4)]
    for vals in (ints, flts, ints):
        for r in pers.run_group(vals):
            np.testing.assert_array_equal(r, np.sum(vals, axis=0))
