"""``Iterate(..., invariants=...)``: a later call of the same loop takes
over the tape an earlier one captured, rebound to its own invariants
(api/loop.py). What makes two calls the same loop, what a rebound tape
reads, and every way in which a tape stays with its own call: always the
right answer, never another call's data."""

import functools
import types

import numpy as np
import pytest

import jax.numpy as jnp

from thrill_tpu.api import Bind, Iterate, RunLocalMock, Zip
from thrill_tpu.api import loop as loop_mod

N = 64


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("THRILL_TPU_LOOP_REPLAY", "THRILL_TPU_LOOP_FORI",
                "THRILL_TPU_FUSE"):
        monkeypatch.delenv(var, raising=False)


def _axpy(xw, p):
    return xw[0] * p[0] + xw[1]


def _add(a, b):
    return a + b


def _step(x, w, p):
    """x <- p * x + w"""
    return Zip(x, w).Map(Bind(_axpy, p))


def _step_twice_w(x, w, p):
    """x <- p * x + 2 w, the doubling a dispatch of its own that reads
    the invariant alone"""
    return Zip(x, Zip(w, w, zip_fn=_add).Cache()).Map(Bind(_axpy, p))


_FOREIGN = {}


def _step_foreign(x, w, p):
    """reads a device array that is neither carry nor invariant"""
    return Zip(Zip(x, w).Map(Bind(_axpy, p)), _FOREIGN["dia"],
               zip_fn=_add)


def dense(x, w, p, n, twice=False):
    for _ in range(n):
        x = x * p + (2 * w if twice else w)
    return x


def call(ctx, body, x, w, p, n, **kw):
    got = Iterate(ctx, body, ctx.Distribute(x), n, name="axpy",
                  invariants=(ctx.Distribute(w).Cache().Keep(n),
                              np.array([p])), **kw)
    return np.asarray(got.AllGather(), dtype=np.float64)


def stats(ctx):
    s = ctx.overall_stats()
    return (s["loop_plan_builds"], s["loop_plan_rebinds"],
            s["loop_replay_fallbacks"])


def vectors(seed):
    rng = np.random.default_rng(seed)
    return rng.normal(size=N), rng.normal(size=N)


def test_a_rebound_tape_reads_its_own_calls_invariants_and_carry():
    def job(ctx):
        for k, seed in enumerate((1, 2, 3)):
            x, w = vectors(seed)
            got = call(ctx, _step, x, w, 0.5, 5)
            np.testing.assert_allclose(got, dense(x, w, 0.5, 5),
                                       rtol=1e-13)
            assert stats(ctx) == (1, k, 0)
        # a kept tape holds no call's buffers between calls
        (plan,) = ctx.mesh_exec.loop_plans.values()
        assert plan._inv is None and plan._pro is None

    RunLocalMock(job, 1)


def test_the_number_of_iterations_is_an_operand_of_the_kept_program():
    def job(ctx):
        x, w = vectors(4)
        for k, n in enumerate((4, 7, 2, 1)):
            got = call(ctx, _step, x, w, 0.9, n)
            np.testing.assert_allclose(got, dense(x, w, 0.9, n),
                                       rtol=1e-13)
            assert stats(ctx) == (1, k, 0)
        # one whole-loop program for every n
        assert sum(1 for key in ctx.mesh_exec._cache
                   if key[0] == "loop_fori") == 1

    RunLocalMock(job, 1)


def test_a_prologue_runs_once_per_call_on_the_calls_own_invariant():
    def job(ctx):
        for k, seed in enumerate((5, 6)):
            x, w = vectors(seed)
            got = call(ctx, _step_twice_w, x, w, 0.5, 4)
            np.testing.assert_allclose(
                got, dense(x, w, 0.5, 4, twice=True), rtol=1e-13)
            assert stats(ctx) == (1, k, 0)
        (plan,) = ctx.mesh_exec.loop_plans.values()
        assert len(plan.prologue) == 1 and len(plan.calls) == 1

    RunLocalMock(job, 1)


@pytest.mark.parametrize("what", ["host_value", "carry_shape",
                                  "invariant_shape", "name"])
def test_another_loop_is_captured_afresh(what):
    def job(ctx):
        x, w = vectors(7)
        call(ctx, _step, x, w, 0.5, 3)
        if what == "host_value":
            got = call(ctx, _step, x, w, 0.25, 3)
            want = dense(x, w, 0.25, 3)
        elif what == "name":
            got = np.asarray(Iterate(
                ctx, _step, ctx.Distribute(x), 3, name="other",
                invariants=(ctx.Distribute(w).Cache().Keep(3),
                            np.array([0.5]))).AllGather())
            want = dense(x, w, 0.5, 3)
        else:
            # 64 -> 48 items: other host-known counts, the same capacity
            x2, w2 = x[:48], w[:48]
            got = call(ctx, _step, x2, w2, 0.5, 3)
            want = dense(x2, w2, 0.5, 3)
        np.testing.assert_allclose(got, want, rtol=1e-13)
        assert stats(ctx) == (2, 0, 0)

    RunLocalMock(job, 1)


def test_a_body_with_closure_cells_keeps_no_tape():
    def job(ctx):
        for seed, p in ((8, 0.5), (9, 0.25)):
            x, w = vectors(seed)

            def body(c, w_dia):
                return Zip(c, w_dia).Map(Bind(_axpy, np.array([p])))

            got = np.asarray(Iterate(
                ctx, body, ctx.Distribute(x), 3, name="axpy",
                invariants=(ctx.Distribute(w).Cache().Keep(3),)
            ).AllGather())
            np.testing.assert_allclose(got, dense(x, w, p, 3), rtol=1e-13)
        assert stats(ctx) == (2, 0, 0)
        assert not ctx.mesh_exec.loop_plans

    RunLocalMock(job, 1)


class _Damped:
    """a body that carries its parameter in ``self``"""

    def __init__(self, p):
        self.p = p

    def step(self, c, w_dia):
        return Zip(c, w_dia).Map(Bind(_axpy, np.array([self.p])))

    __call__ = step


def _by_default(p):
    def body(c, w_dia, p=p):
        return Zip(c, w_dia).Map(Bind(_axpy, np.array([p])))
    return body


def _by_kwdefault(p):
    def body(c, w_dia, *, p=p):
        return Zip(c, w_dia).Map(Bind(_axpy, np.array([p])))
    return body


def _with_p(c, w_dia, p):
    return Zip(c, w_dia).Map(Bind(_axpy, np.array([p])))


_P = 0.5


def _by_global(c, w_dia):
    return Zip(c, w_dia).Map(Bind(_axpy, np.array([_P])))


_BODIES_THAT_CARRY = {
    # one bytecode, one qualified name, another value in each: a key
    # made of the code would hand the second the first one's tape
    "method": lambda p: _Damped(p).step,
    "callable_object": _Damped,
    "default": _by_default,
    "kwdefault": _by_kwdefault,
    "partial": lambda p: functools.partial(_with_p, p=p),
    # the same code object over other globals: a function of its own
    "same_code": lambda p: types.FunctionType(
        _by_global.__code__, dict(globals(), _P=p), "_by_global"),
}


@pytest.mark.parametrize("how", sorted(_BODIES_THAT_CARRY))
def test_a_body_that_carries_a_value_never_replays_anothers_tape(how):
    make = _BODIES_THAT_CARRY[how]

    def job(ctx):
        for k, (seed, p) in enumerate(((20, 0.5), (21, 0.25))):
            x, w = vectors(seed)
            got = np.asarray(Iterate(
                ctx, make(p), ctx.Distribute(x), 3, name="axpy",
                invariants=(ctx.Distribute(w).Cache().Keep(3),)
            ).AllGather())
            np.testing.assert_allclose(got, dense(x, w, p, 3), rtol=1e-13)
            assert stats(ctx) == (k + 1, 0, 0)
        if how != "same_code":
            assert not ctx.mesh_exec.loop_plans

    RunLocalMock(job, 1)


def test_a_kept_tape_is_keyed_by_the_body_object_and_holds_it():
    def job(ctx):
        x, w = vectors(22)
        call(ctx, _step, x, w, 0.5, 3)
        (key,) = ctx.mesh_exec.loop_plans
        assert key[0] is _step

    RunLocalMock(job, 1)


def test_a_tape_that_read_a_foreign_device_array_stays_with_its_call():
    def job(ctx):
        for k, seed in enumerate((10, 11)):
            x, w = vectors(seed)
            extra = np.full(N, float(seed))
            _FOREIGN["dia"] = ctx.Distribute(extra).Cache().Keep(8)
            got = call(ctx, _step_foreign, x, w, 0.5, 3)
            want = x
            for _ in range(3):
                want = want * 0.5 + w + extra
            np.testing.assert_allclose(got, want, rtol=1e-13)
            assert stats(ctx) == (k + 1, 0, 0)
        assert not ctx.mesh_exec.loop_plans

    try:
        RunLocalMock(job, 1)
    finally:
        _FOREIGN.clear()


def test_a_failed_rebind_falls_back_loudly_to_a_fresh_capture(monkeypatch):
    def job(ctx):
        x, w = vectors(12)
        call(ctx, _step, x, w, 0.5, 3)

        def boom(self, inv_leaves):
            raise RuntimeError("prologue failed")

        monkeypatch.setattr(loop_mod.LoopPlan, "bind", boom)
        x, w = vectors(13)
        got = call(ctx, _step, x, w, 0.5, 3)
        np.testing.assert_allclose(got, dense(x, w, 0.5, 3), rtol=1e-13)
        assert stats(ctx) == (2, 0, 1)

    RunLocalMock(job, 1)


def test_without_replay_the_invariants_still_reach_the_body(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_LOOP_REPLAY", "0")

    def job(ctx):
        for seed in (14, 15):
            x, w = vectors(seed)
            got = call(ctx, _step, x, w, 0.5, 3)
            np.testing.assert_allclose(got, dense(x, w, 0.5, 3),
                                       rtol=1e-13)
        assert stats(ctx) == (0, 0, 0)

    RunLocalMock(job, 1)


def test_per_iteration_replay_of_a_rebound_tape(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_LOOP_FORI", "0")

    def job(ctx):
        for k, seed in enumerate((16, 17)):
            x, w = vectors(seed)
            got = call(ctx, _step_twice_w, x, w, 0.5, 4)
            np.testing.assert_allclose(
                got, dense(x, w, 0.5, 4, twice=True), rtol=1e-13)
        s = ctx.overall_stats()
        assert stats(ctx) == (1, 1, 0)
        assert s["loop_replays"] == 3 + 4 and s["loop_fori_iters"] == 0

    RunLocalMock(job, 1)


def test_a_callable_among_the_invariants_cannot_be_signed():
    def update(c):
        return {"x": c["x"] * 0.5 + 1.0}

    def job(ctx):
        fn = ctx.mesh_exec.jit_cached(("test_rebind_update",), update)
        for x0 in (0.0, 3.0):
            got = Iterate(ctx, _tree_step, {"x": jnp.full(4, x0)}, 5,
                          name="tree", invariants=(fn,))
            want = np.full(4, x0)
            for _ in range(5):
                want = want * 0.5 + 1.0
            np.testing.assert_allclose(np.asarray(got["x"]), want)
        # captured in both calls, kept in neither
        assert stats(ctx) == (2, 0, 0)
        assert not ctx.mesh_exec.loop_plans

    RunLocalMock(job, 1)


def _tree_step(c, fn):
    return fn(c)
