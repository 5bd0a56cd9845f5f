"""Whole-solve on-device Krylov: ONE jitted ``lax.while_loop`` per solve.

The host loops in :mod:`repro.solve.krylov` dispatch every matvec, exchange
and reduction from Python, so at production iteration counts the per-call
host overhead (``T_launch`` in :mod:`repro.core.perfmodel`) bounds latency
regardless of the communication strategy.  This module compiles the ENTIRE
solve -- exchange stages, (masked, possibly split-phase) blocked-ELL SpMV,
hierarchical dot products, convergence and breakdown control flow -- into a
single jitted ``shard_map`` program whose iteration is a ``lax.while_loop``
body: zero host round-trips between iterations, one launch per solve.  This
is the jax analogue of pre-armed triggered-operation schedules (see
``docs/paper_mapping.md``).

Building blocks (all pure per-shard callables + operand pytrees):

* :class:`repro.solve.operator.TraceableOperator` -- the matvec
  (:func:`repro.solve.operator.traceable_operator` lowers either executor
  flavor; overlap mode expresses the split-phase decomposition inside the
  loop body);
* :func:`repro.solve.reductions.traceable_dot` -- the hierarchical
  reduction tree;
* :class:`repro.comm.strategies.TraceableExchange` -- the exchange stages
  (inside the operator).

Semantics mirror the host solvers statement-for-statement -- same breakdown
guards, stall window, best-iterate tracking and one-restart policy -- except
that control flow is data: branches become ``jnp.where`` selects and the
restart re-dispatches the SAME compiled program from the best iterate (the
program's init section IS the host's true-residual recompute).  Residual
histories are bitwise identical across strategies and barrier-vs-overlap
execution on the fused path, and match the host oracle to float32 scalar
precision (the host accumulates its scalars in float64).

Compiled programs live in the module fused-program cache
(``repro.comm.cache_stats().fused_*``), keyed by (pattern fingerprint,
solver, strategy, codec, overlap, kernel flavor, dtype, maxiter, ...): a
whole solve re-runs with zero plan work and zero retracing, and cache
pressure behaves like every other compiled artifact
(:func:`repro.comm.strategies.set_cache_limits`).

``verify=True`` operators carry their wire-integrity checks through the
loop: per-hop violations accumulate (elementwise max) in the loop carry and
surface after the solve as the same structured
:class:`repro.comm.faults.ExchangeIntegrityError` the host path raises.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np

from repro.comm import strategies as comm_strategies
from repro.comm.topology import shard_ranks
from repro.comm.faults import (
    ExchangeIntegrityError,
    HealthTracker,
    advise_alternative,
    run_ladder,
)
from repro.solve.krylov import (
    STALL_WINDOW,
    SolveResult,
    _finish_status,
    _recovery_baseline,
)
from repro.solve.operator import traceable_operator
from repro.solve.reductions import traceable_dot
from repro.trace import scope, span

# status codes carried through the loop (mapped back to the host solvers'
# status strings on exit)
_CONV = 0
_MAXITER = 1
_INDEF = 2
_NONFIN = 3
_STAG = 4
_RHO = 5
_OMEGA = 6
_DENOM = 7
_TT = 8

_STATUS_STR = {
    _CONV: "converged",
    _MAXITER: "maxiter",
    _INDEF: "breakdown:indefinite",
    _NONFIN: "breakdown:nonfinite",
    _STAG: "stagnation",
    _RHO: "breakdown:rho",
    _OMEGA: "breakdown:omega",
    _DENOM: "breakdown:denom",
    _TT: "breakdown:tt",
}

#: statuses that trigger the one-restart-from-best-iterate policy (matching
#: the host loops: CG restarts only on nonfinite/stagnation -- indefiniteness
#: ends the solve -- while BiCGStab restarts on every breakdown flavor)
_RESTART = {
    "cg": frozenset({_NONFIN, _STAG}),
    "bicgstab": frozenset({_NONFIN, _STAG, _RHO, _OMEGA, _DENOM, _TT}),
}


def _cg_body(mv, dot, tol, bnorm, hist_len):
    """The CG iteration as a pure ``lax.while_loop`` body (where-selected
    control flow; statement-for-statement twin of :func:`...krylov.cg`)."""
    import jax.numpy as jnp

    def body(c):
        (x, r, p, rs, best, best_x, best_it, it, k, hist, status, done,
         mvc, viols) = c
        Ap, vv = mv(p, mvc)
        mvc = mvc + 1
        viols = jnp.maximum(viols, vv) if vv.size else viols
        pAp = dot(p, Ap)
        indef = pAp <= 0.0
        alpha = rs / jnp.where(indef, jnp.ones_like(pAp), pAp)
        x1 = x + alpha * p
        r1 = r - alpha * Ap
        rs_new = dot(r1, r1)
        relres = jnp.sqrt(jnp.maximum(rs_new, 0.0)) / bnorm
        it1 = jnp.where(indef, it, it + 1)
        conv = (~indef) & (relres <= tol)
        improved = (~indef) & (~conv) & (relres < best)
        best1 = jnp.where(improved, relres, best)
        best_x1 = jnp.where(improved, x1, best_x)
        best_it1 = jnp.where(improved, it1, best_it)
        nonfin = (~indef) & (~conv) & (~jnp.isfinite(relres))
        stall = (~indef) & (~conv) & (~nonfin) & (
            it1 - best_it1 >= STALL_WINDOW
        )
        done1 = indef | conv | nonfin | stall
        status1 = jnp.where(
            indef, _INDEF,
            jnp.where(conv, _CONV,
                      jnp.where(nonfin, _NONFIN,
                                jnp.where(stall, _STAG, _MAXITER))),
        ).astype(jnp.int32)
        hist1 = jnp.where(indef, hist, hist.at[k].set(relres))
        k1 = jnp.where(indef, k, k + 1)
        x2 = jnp.where(indef, x, x1)
        r2 = jnp.where(indef, r, r1)
        # the search direction only matters on the continue path
        cont = ~done1
        p1 = jnp.where(cont, r1 + (rs_new / rs) * p, p)
        rs1 = jnp.where(cont, rs_new, rs)
        return (x2, r2, p1, rs1, best1, best_x1, best_it1, it1, k1, hist1,
                status1, done1, mvc, viols)

    return body


def _bicgstab_body(mv, dot, tol, bnorm, rhat, rhat_nrm, eps, hist_len):
    """The BiCGStab iteration as a pure loop body (twin of
    :func:`...krylov.bicgstab`; ``rhat`` is fixed per dispatch, a restart is
    a fresh dispatch)."""
    import jax.numpy as jnp

    def nz(a):
        return jnp.where(a == 0, jnp.ones_like(a), a)

    def body(c):
        (x, r, p, v, rho, alpha, omega, relprev, best, best_x, best_it, it,
         k, hist, status, done, mvc, viols) = c
        rho_new = dot(rhat, r)
        r_nrm = relprev * bnorm
        bad_rho = jnp.abs(rho_new) <= eps * rhat_nrm * r_nrm
        bad_omega = (~bad_rho) & (jnp.abs(omega) <= eps * jnp.abs(alpha))
        ok1 = (~bad_rho) & (~bad_omega)
        beta = (rho_new / nz(rho)) * (alpha / nz(omega))
        p1 = jnp.where(ok1, r + beta * (p - omega * v), p)
        v1m, vva = mv(p1, mvc)
        v1 = jnp.where(ok1, v1m, v)
        denom = dot(rhat, v1m)
        bad_denom = ok1 & (jnp.abs(denom) <= eps * jnp.abs(rho_new))
        ok2 = ok1 & (~bad_denom)
        alpha1 = jnp.where(ok2, rho_new / nz(denom), alpha)
        s = jnp.where(ok2, r - alpha1 * v1m, r)
        it1 = jnp.where(ok2, it + 1, it)
        snorm = jnp.sqrt(jnp.maximum(dot(s, s), 0.0))
        rel_s = snorm / bnorm
        s_conv = ok2 & (rel_s <= tol)
        t1, vvb = mv(s, mvc + ok1.astype(jnp.int32))
        tt = dot(t1, t1)
        bad_tt = ok2 & (~s_conv) & (tt <= (eps * snorm) ** 2)
        ok3 = ok2 & (~s_conv) & (~bad_tt)
        omega1 = jnp.where(ok3, dot(t1, s) / nz(tt), omega)
        x_sc = x + alpha1 * p1
        x1 = x_sc + omega1 * s
        r1 = s - omega1 * t1
        relres = jnp.sqrt(jnp.maximum(dot(r1, r1), 0.0)) / bnorm
        conv = ok3 & (relres <= tol)
        improved = ok3 & (~conv) & (relres < best)
        best1 = jnp.where(improved, relres, best)
        best_x1 = jnp.where(improved, x1, best_x)
        best_it1 = jnp.where(improved, it1, best_it)
        nonfin = ok3 & (~conv) & (~jnp.isfinite(relres))
        stall = ok3 & (~conv) & (~nonfin) & (it1 - best_it1 >= STALL_WINDOW)
        done1 = (
            bad_rho | bad_omega | bad_denom | s_conv | bad_tt | conv
            | nonfin | stall
        )
        status1 = jnp.where(
            bad_rho, _RHO,
            jnp.where(bad_omega, _OMEGA,
            jnp.where(bad_denom, _DENOM,
            jnp.where(s_conv, _CONV,
            jnp.where(bad_tt, _TT,
            jnp.where(conv, _CONV,
            jnp.where(nonfin, _NONFIN,
            jnp.where(stall, _STAG, _MAXITER))))))),
        ).astype(jnp.int32)
        # a history entry lands only on the paths the host appends on: the
        # half-step convergence exit and the full step (step 8)
        wrote = s_conv | ok3
        hist_val = jnp.where(s_conv, rel_s, relres)
        hist1 = jnp.where(wrote, hist.at[k].set(hist_val), hist)
        k1 = jnp.where(wrote, k + 1, k)
        relprev1 = jnp.where(wrote, hist_val, relprev)
        x2 = jnp.where(s_conv, x_sc, jnp.where(ok3, x1, x))
        r2 = jnp.where(ok3, r1, r)
        p2 = jnp.where(ok1, p1, p)
        rho1 = jnp.where(ok3, rho_new, rho)
        # matvec count matches the host's early-out structure per path
        mvc = mvc + ok1.astype(jnp.int32) + (ok2 & ~s_conv).astype(jnp.int32)
        vv = jnp.maximum(vva, vvb)
        viols = jnp.maximum(viols, vv) if vv.size else viols
        return (x2, r2, p2, v1, rho1, alpha1, omega1, relprev1, best1,
                best_x1, best_it1, it1, k1, hist1, status1, done1, mvc,
                viols)

    return body


def _in_scope(name: str, fn):
    """``fn`` with the operations it traces under device scope ``name``."""

    def scoped(*args):
        with scope(name):
            return fn(*args)

    return scoped


def _build_fused(top, shard_dot, solver: str, hist_len: int, eps: float,
                 nviol: int, checkpoint_every: Optional[int] = None,
                 gate=None, resume: bool = False):
    """Compile ONE jitted shard_map program: init + ``lax.while_loop``.

    Signature (all device inputs ``[nranks, ...]`` under ``P(WORLD_AXES)``):
    ``fn(b, x0, tol[g,1], max_it[g,1], *operands)``.  The iteration cap is a
    TRACED scalar -- only the history buffer length is static -- so a restart
    re-dispatch with the remaining budget reuses the same executable.

    ``checkpoint_every=N`` carries a solver-state snapshot in the loop
    carry, refreshed every N clean iterations (zero extra dispatches), and
    appends it to the outputs as four packed arrays -- the fuel for
    host-side resume after an integrity failure.  ``resume=True`` builds the
    companion entry point ``fn(b, ck_vec, ck_f, ck_i, ck_hist, tol,
    max_it, *operands)`` that reconstructs the carry from a checkpoint and
    enters the SAME loop body: no init matvec, history/iteration/matvec
    counters continue exactly where the snapshot left them, so a resumed
    trajectory is bitwise the clean run's continuation.  ``gate`` --
    ``(top_clean, active_calls)`` -- selects per matvec call index between
    the faulted and clean lowerings of the operator, which is what lets a
    ``FaultPlan.active_calls`` schedule interrupt a fused solve mid-loop.
    With all three off, the trace is unchanged from the pre-resume program.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comm.topology import WORLD_AXES

    ce = checkpoint_every

    def make_mv(ops):
        if gate is None:
            def mv(vec, call_idx):
                return top.matvec_verified(vec, *ops)
        else:
            top_clean, active = gate

            def mv(vec, call_idx):
                wf, vf = top.matvec_verified(vec, *ops)
                wc, vc = top_clean.matvec_verified(vec, *ops)
                use = jnp.zeros((), bool)
                for c in active:
                    use = use | (call_idx == jnp.int32(c))
                w = jnp.where(use, wf, wc)
                vv = jnp.where(use, vf, vc) if vf.size else vf
                return w, vv

        return mv

    def global_clean(jnp_mod, viols):
        """True iff NO shard has recorded a violation.  ``viols`` is the one
        per-shard carry component (each chip verifies its own halo), so any
        checkpoint decision derived from it must be all-reduced -- otherwise
        shards that did not see the corrupted halo keep snapshotting
        post-fault state and the harvested checkpoint mixes iterations."""
        return jax.lax.pmax(jnp_mod.max(viols), WORLD_AXES) == 0.0

    def run_loop(jnp_mod, carry, body, it_idx, done_idx, viol_idx, max_it,
                 snapshot):
        """The while_loop, optionally wrapped with the checkpoint carry."""

        def cond(c):
            return (~c[done_idx]) & (c[it_idx] < max_it)

        if ce is None:
            return jax.lax.while_loop(cond, body, carry), None

        ck0 = snapshot(carry)

        def body_ck(cc):
            inner, ck = cc
            prev_it = inner[it_idx]
            out = body(inner)
            take = (
                (~out[done_idx])
                & (out[it_idx] % jnp_mod.int32(ce) == 0)
                & (out[it_idx] > prev_it)
                & global_clean(jnp_mod, out[viol_idx])
            )
            fresh = snapshot(out)
            new_ck = tuple(
                jnp_mod.where(take, a, b) for a, b in zip(fresh, ck)
            )
            return out, new_ck

        def cond_ck(cc):
            return cond(cc[0])

        return jax.lax.while_loop(cond_ck, body_ck, (carry, ck0))

    def solve_from(b, carry_parts, tolt, maxitt, ops):
        """Shared tail: build the body, run the loop, pack the outputs."""
        tol = tolt[0, 0]
        max_it = maxitt[0, 0]
        mv = make_mv(ops)

        def dot(u, w):
            return shard_dot(u, w)

        (carry, bnorm, rhat, rhat_nrm, fdt) = carry_parts(mv, dot, tol)

        # the vector updates; the matvec and the dots inside carry their
        # own scopes (spmv.*, exchange.*, solve.reduce)
        if solver == "cg":
            body = _in_scope("solve.update", _cg_body(mv, dot, tol, bnorm, hist_len))
            best_x_idx, it_idx = 5, 7
            k_idx, st_idx, done_idx, mv_idx, viol_idx = 8, 10, 11, 12, 13

            def snapshot(c):
                flag = global_clean(jnp, c[viol_idx]).astype(jnp.int32)
                ck_vec = jnp.stack([c[0], c[1], c[2], c[best_x_idx]], axis=1)
                ck_f = jnp.stack([c[3], c[4]])[None].astype(fdt)
                ck_i = jnp.stack(
                    [c[it_idx], c[k_idx], c[6], c[mv_idx], flag]
                )[None].astype(jnp.int32)
                return ck_vec, ck_f, ck_i, c[9][None]
        else:
            body = _in_scope("solve.update", _bicgstab_body(
                mv, dot, tol, bnorm, rhat, rhat_nrm,
                jnp.asarray(eps, fdt), hist_len,
            ))
            best_x_idx, it_idx = 9, 11
            k_idx, st_idx, done_idx, mv_idx, viol_idx = 12, 14, 15, 16, 17

            def snapshot(c):
                flag = global_clean(jnp, c[viol_idx]).astype(jnp.int32)
                ck_vec = jnp.stack(
                    [c[0], c[1], c[2], c[3], c[best_x_idx], rhat], axis=1
                )
                ck_f = jnp.stack(
                    [c[4], c[5], c[6], c[7], c[8], rhat_nrm]
                )[None].astype(fdt)
                ck_i = jnp.stack(
                    [c[it_idx], c[k_idx], c[10], c[mv_idx], flag]
                )[None].astype(jnp.int32)
                return ck_vec, ck_f, ck_i, c[13][None]

        out, ck = run_loop(jnp, carry, body, it_idx, done_idx, viol_idx,
                           max_it, snapshot)

        def tile(a, dt):
            return jnp.reshape(a.astype(dt), (1, 1))

        packed = (
            out[0],                                 # x        [1, L]
            out[best_x_idx],                        # best_x   [1, L]
            out[k_idx + 1][None],                   # hist     [1, hist_len]
            tile(out[it_idx], jnp.int32),           # it       [1, 1]
            tile(out[k_idx], jnp.int32),            # entries  [1, 1]
            tile(out[st_idx], jnp.int32),           # status   [1, 1]
            tile(out[mv_idx], jnp.int32),           # matvecs  [1, 1]
            out[viol_idx][None],                    # viols    [1, nviol]
        )
        if ce is not None:
            packed = packed + tuple(ck)
        return packed

    def program(b, x0, tolt, maxitt, *ops):
        fdt = b.dtype

        def carry_parts(mv, dot, tol):
            one = jnp.asarray(1.0, fdt)
            Ax, vv0 = mv(x0, jnp.int32(0))
            r = b - Ax
            bnorm = jnp.sqrt(jnp.maximum(dot(b, b), 0.0))
            rs = dot(r, r)
            rel0 = jnp.sqrt(jnp.maximum(rs, 0.0)) / bnorm
            hist = jnp.full((hist_len,), jnp.nan, fdt).at[0].set(rel0)
            viols = jnp.zeros((nviol,), jnp.float32)
            if vv0.size:
                viols = jnp.maximum(viols, vv0)
            done0 = rel0 <= tol
            status0 = jnp.where(done0, _CONV, _MAXITER).astype(jnp.int32)
            i0 = jnp.int32(0)
            k0 = jnp.int32(1)
            mv0 = jnp.int32(1)
            if solver == "cg":
                #        x,  r, p, rs, best, best_x, best_it, it, k
                carry = (x0, r, r, rs, rel0, x0, i0, i0, k0, hist, status0,
                         done0, mv0, viols)
                return carry, bnorm, None, None, fdt
            zero = jnp.zeros_like(b)
            #        x,  r, p,    v,    rho, alpha, omega, relprev, best,
            #        best_x, best_it, it, k
            carry = (x0, r, zero, zero, one, one, one, rel0, rel0, x0, i0,
                     i0, k0, hist, status0, done0, mv0, viols)
            return carry, bnorm, r, rel0 * bnorm, fdt

        return solve_from(b, carry_parts, tolt, maxitt, ops)

    def program_resume(b, ckv, ckf, cki, ckh, tolt, maxitt, *ops):
        fdt = b.dtype

        def carry_parts(mv, dot, tol):
            bnorm = jnp.sqrt(jnp.maximum(dot(b, b), 0.0))
            it = cki[0, 0]
            k = cki[0, 1]
            best_it = cki[0, 2]
            mvc = cki[0, 3]
            hist = ckh[0]
            viols = jnp.zeros((nviol,), jnp.float32)
            done0 = jnp.zeros((), bool)
            status0 = jnp.asarray(_MAXITER, jnp.int32)
            x, r, p = ckv[:, 0], ckv[:, 1], ckv[:, 2]
            if solver == "cg":
                rs, best = ckf[0, 0], ckf[0, 1]
                best_x = ckv[:, 3]
                carry = (x, r, p, rs, best, best_x, best_it, it, k, hist,
                         status0, done0, mvc, viols)
                return carry, bnorm, None, None, fdt
            rho, alpha, omega = ckf[0, 0], ckf[0, 1], ckf[0, 2]
            relprev, best = ckf[0, 3], ckf[0, 4]
            v, best_x, rhat = ckv[:, 3], ckv[:, 4], ckv[:, 5]
            carry = (x, r, p, v, rho, alpha, omega, relprev, best, best_x,
                     best_it, it, k, hist, status0, done0, mvc, viols)
            return carry, bnorm, rhat, ckf[0, 5], fdt

        return solve_from(b, carry_parts, tolt, maxitt, ops)

    fn = program_resume if resume else program
    n_in = (7 if resume else 4) + len(top.operands)
    n_out = 8 if ce is None else 12
    return jax.jit(
        jax.shard_map(
            fn,
            mesh=top.mesh,
            in_specs=(P(WORLD_AXES),) * n_in,
            out_specs=(P(WORLD_AXES),) * n_out,
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Host wrapper: cache, dispatch, restart policy, SolveResult assembly
# ---------------------------------------------------------------------------


def _fused_entry(op, solver: str, maxiter: int, dtype, compressor,
                 checkpoint_every: Optional[int] = None,
                 resume: bool = False):
    """Fetch (or build) the compiled whole-solve program for ``op``; return
    it with ``op``'s own lowering.

    The key is derived from the operator's configuration and sparsity
    alone, so every operator that shares them shares one compiled program.
    The operands it runs on (device blocks, plan arrays, masks) are always
    the calling operator's: :func:`_lowered` lowers each operator instance
    once.  ``resume=True`` fetches the checkpoint-resume companion entry
    point (requires ``checkpoint_every``); the two share a key prefix but
    compile separately.
    """
    faults = getattr(op, "faults", None)
    mesh = getattr(op, "mesh", None)
    mesh_key = comm_strategies._mesh_key(mesh) if mesh is not None else None
    key = (
        "fused", solver, op.partition.pattern.fingerprint(), op.strategy,
        op.wire, bool(op.overlap), bool(getattr(op, "use_pallas", False)),
        bool(getattr(op, "verify", False)),
        faults.fingerprint() if faults is not None else None,
        op.message_cap_bytes, mesh_key, int(maxiter), str(dtype),
        None if compressor is None else str(compressor),
        checkpoint_every, "resume" if resume else "fwd",
    )

    top = _lowered(op)

    def build():
        gate = None
        if faults is not None and faults.active_calls is not None:
            # call-indexed fault schedule: trace BOTH lowerings and select
            # per matvec call, so a transient plan can interrupt the loop
            # mid-solve (operand layouts are identical -- fault masks are
            # trace constants and plan arrays ignore faults)
            top_clean = traceable_operator(dataclasses.replace(op, faults=None))
            gate = (top_clean, faults.active_calls)
        shard_dot = traceable_dot(compressor)
        nviol = len(top.verifier.checks) if top.verifier is not None else 1
        eps = float(np.finfo(dtype).eps)
        hist_len = int(maxiter) + 1
        return _build_fused(top, shard_dot, solver, hist_len, eps, nviol,
                            checkpoint_every=checkpoint_every, gate=gate,
                            resume=resume)

    return comm_strategies.fused_cached(key, build), top


def _lowered(op):
    """:func:`traceable_operator` of ``op``, once per operator instance."""
    top = op.__dict__.get("_fused_lowering")
    if top is None:
        top = op._fused_lowering = traceable_operator(op)
    return top


def _limits(top, tol: float, max_it: int, dtype) -> tuple:
    """Per-rank tolerance and iteration cap, placed like every operand."""
    g = top.topo.nranks
    return (
        shard_ranks(np.full((g, 1), tol, dtype), top.mesh),
        shard_ranks(np.full((g, 1), max_it, np.int32), top.mesh),
    )


def _dispatch(fn, top, b_dev, x0_dev, tol: float, max_it: int, dtype):
    with span("solve.loop"):
        outs = _raw_forward(fn, top, b_dev, x0_dev, tol, max_it, dtype)
    x, best_x, hist, it, k, status, mvc, viols = outs[:8]
    with span("solve.readback"):
        if top.verifier is not None:
            top.verifier.raise_viols(np.asarray(viols))
        k = int(np.asarray(k)[0, 0])
        return (
            x,
            best_x,
            [float(h) for h in np.asarray(hist)[0, :k]],
            int(np.asarray(it)[0, 0]),
            int(np.asarray(status)[0, 0]),
            int(np.asarray(mvc)[0, 0]),
        )


class _Checkpoint(NamedTuple):
    """Harvested solver-state snapshot (device arrays + host counters)."""

    vec: object  # [g, nvec, L]
    f: object    # [g, nf]
    i: object    # [g, 5] int32: it, k, best_it, mvc, valid
    hist: object  # [g, hist_len]
    it: int
    k: int
    mvc: int


def _harvest(prev: Optional[_Checkpoint], outs) -> Optional[_Checkpoint]:
    """Keep the newest VALID checkpoint across dispatches (a failed resume
    attempt may still have advanced past the one it started from)."""
    ckv, ckf, cki, ckh = outs[8:12]
    i_np = np.asarray(cki)
    if int(i_np[0, 4]) != 1:
        return prev
    it = int(i_np[0, 0])
    if prev is not None and prev.it >= it:
        return prev
    return _Checkpoint(ckv, ckf, cki, ckh, it=it, k=int(i_np[0, 1]),
                       mvc=int(i_np[0, 3]))


def _raw_forward(fn, top, b_dev, x0_dev, tol: float, max_it: int, dtype):
    return fn(b_dev, x0_dev, *_limits(top, tol, max_it, dtype), *top.operands)


def _raw_resume(fn, top, b_dev, ck: _Checkpoint, tol: float, max_it: int,
                dtype):
    return fn(b_dev, ck.vec, ck.f, ck.i, ck.hist,
              *_limits(top, tol, max_it, dtype), *top.operands)


def _viol_error(top, viols_np):
    """The structured error a violation vector encodes, or None if clean."""
    if top.verifier is None:
        return None
    try:
        top.verifier.raise_viols(viols_np)
    except ExchangeIntegrityError as e:
        return e
    return None


def _unpack(outs):
    hist_k = int(np.asarray(outs[4])[0, 0])
    return (
        outs[0],
        outs[1],
        [float(h) for h in np.asarray(outs[2])[0, :hist_k]],
        int(np.asarray(outs[3])[0, 0]),
        int(np.asarray(outs[5])[0, 0]),
        int(np.asarray(outs[6])[0, 0]),
    )


def _fused_solve(op, b, x0, tol: float, maxiter: int, reductions,
                 solver: str, checkpoint_every: Optional[int] = None
                 ) -> SolveResult:
    compressor = getattr(reductions, "compressor", None)
    b = np.asarray(b)
    g, L = op.topo.nranks, op.rows_per_rank
    if b.shape != (g, L):
        raise ValueError(f"b must be [{g}, {L}], got {tuple(b.shape)}")
    if checkpoint_every is not None and checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}"
        )
    rc0 = _recovery_baseline(op)
    if not np.any(b):
        # mirror the host solvers' zero-rhs early return (same
        # _finish_status routing)
        return SolveResult(x=np.zeros_like(b), converged=True, iterations=0,
                           residuals=(0.0,), matvecs=0,
                           status=_finish_status("converged", 0, op, rc0))
    dtype = b.dtype
    mesh = getattr(op, "mesh", None) or comm_strategies._default_mesh(op.topo)
    with span("solve.upload"):
        b_dev = shard_ranks(b, mesh)
        x0_dev = shard_ranks(
            np.zeros_like(b) if x0 is None else np.asarray(x0, dtype=dtype), mesh
        )
    # the program always runs the init matvec (for x0=0 it computes
    # b - A@0 = b exactly); the host loops only count it when x0 is given
    init_mv_adjust = 1 if x0 is None else 0
    if checkpoint_every is not None:
        return _fused_solve_resumable(
            op, b, b_dev, x0_dev, tol, maxiter, dtype, compressor, solver,
            checkpoint_every, rc0, init_mv_adjust,
        )
    fn, top = _fused_entry(op, solver, maxiter, dtype, compressor)

    x, best_x, hist, it, status, mvc, = _dispatch(
        fn, top, b_dev, x0_dev, tol, maxiter, dtype
    )
    restarts = 0
    matvecs = mvc - init_mv_adjust
    if status in _RESTART[solver]:
        bad = _STATUS_STR[status]
        restarts = 1
        # one restart from the best iterate: the program's init section IS
        # the host's true-residual recompute (r = b - A x_best), and its
        # hist[0] is the host's restart history entry
        x, _, hist2, it2, status2, mvc2 = _dispatch(
            fn, top, b_dev, best_x, tol, maxiter - it, dtype
        )
        hist = hist + hist2
        it = it + it2
        matvecs += mvc2
        if not np.isfinite(hist2[0]):
            # the host checks the recomputed residual before re-entering
            # the loop; keep the original breakdown reason
            status_str, converged = bad, False
        elif status2 == _CONV:
            status_str, converged = "converged", True
        elif status2 == _MAXITER:
            status_str, converged = "maxiter", False
        else:
            # second trip ends the solve with the new reason (no re-restart)
            status_str, converged = _STATUS_STR[status2], False
    else:
        status_str = _STATUS_STR[status]
        converged = status == _CONV

    with span("solve.download"):
        x = np.asarray(x)
    return SolveResult(
        x=x,
        converged=converged,
        iterations=it,
        residuals=tuple(hist),
        matvecs=matvecs,
        status=_finish_status(status_str, restarts, op, rc0),
        restarts=restarts,
    )


def _fused_solve_resumable(op, b, b_dev, x0_dev, tol: float, maxiter: int,
                           dtype, compressor, solver: str, ce: int, rc0,
                           init_mv_adjust: int) -> SolveResult:
    """The checkpoint/resume host wrapper around the fused program.

    A clean dispatch behaves exactly like the legacy path (the checkpoint
    rides the loop carry -- zero extra dispatches).  On an integrity
    failure the wrapper harvests the newest pre-fault checkpoint and runs
    the recovery ladder where each attempt RESUMES the fused program --
    first on the same (strategy, codec), then demoted, then re-advised --
    so recovery loses at most ``checkpoint_every`` iterations.  If the
    ladder is exhausted it falls back to the host loop (which carries its
    own per-halo ladder) from the same checkpoint.  ``SolveResult.status``
    records ``+resume:<n>``.
    """
    fn, top = _fused_entry(op, solver, maxiter, dtype, compressor, ce)
    outs = _raw_forward(fn, top, b_dev, x0_dev, tol, maxiter,
                        dtype)
    state = {"ck": _harvest(None, outs), "used": False}
    err = _viol_error(top, np.asarray(outs[7]))
    resumes = 0
    final_op = op
    if err is not None:
        health = getattr(op, "health", None)
        if health is None:
            health = HealthTracker()
        health.record_failure(err)

        def attempt(s: str, w: str):
            vop = (
                op if (s == op.strategy and w == op.wire)
                else dataclasses.replace(op, strategy=s, wire=w)
            )
            cur = state["ck"]
            if cur is not None:
                fnv, topv = _fused_entry(vop, solver, maxiter, dtype,
                                         compressor, ce, resume=True)
                o = _raw_resume(fnv, topv, b_dev, cur, tol, maxiter, dtype)
            else:
                fnv, topv = _fused_entry(vop, solver, maxiter, dtype,
                                         compressor, ce)
                o = _raw_forward(fnv, topv, b_dev, x0_dev, tol,
                                 maxiter, dtype)
            state["ck"] = _harvest(state["ck"], o)
            e = _viol_error(topv, np.asarray(o[7]))
            if e is not None:
                raise e
            state["used"] = cur is not None
            return o, vop

        try:
            (outs, final_op), _path = run_ladder(
                attempt,
                strategy=op.strategy,
                wire=op.wire,
                health=health,
                max_retries=getattr(op, "max_retries", 1),
                fallback=getattr(op, "fallback", True),
                choose_alternative=advise_alternative(op.partition.pattern),
            )
        except ExchangeIntegrityError:
            return _host_resume_fallback(op, b, tol, maxiter, solver,
                                         state["ck"], rc0, init_mv_adjust)
        resumes = 1 if state["used"] else 0

    x, best_x, hist, it, status, mvc = _unpack(outs)
    restarts = 0
    matvecs = mvc - init_mv_adjust
    if status in _RESTART[solver]:
        bad = _STATUS_STR[status]
        restarts = 1
        fnf, topf = _fused_entry(final_op, solver, maxiter, dtype,
                                 compressor, ce)
        o2 = _raw_forward(fnf, topf, b_dev, best_x, tol, maxiter - it, dtype)
        e2 = _viol_error(topf, np.asarray(o2[7]))
        if e2 is not None:
            raise e2
        x, _, hist2, it2, status2, mvc2 = _unpack(o2)
        hist = hist + hist2
        it = it + it2
        matvecs += mvc2
        if not np.isfinite(hist2[0]):
            status_str, converged = bad, False
        elif status2 == _CONV:
            status_str, converged = "converged", True
        elif status2 == _MAXITER:
            status_str, converged = "maxiter", False
        else:
            status_str, converged = _STATUS_STR[status2], False
    else:
        status_str = _STATUS_STR[status]
        converged = status == _CONV

    if resumes:
        status_str += f"+resume:{resumes}"
    return SolveResult(
        x=np.asarray(x),
        converged=converged,
        iterations=it,
        residuals=tuple(hist),
        matvecs=matvecs,
        status=_finish_status(status_str, restarts, op, rc0),
        restarts=restarts,
    )


def _host_resume_fallback(op, b, tol: float, maxiter: int, solver: str,
                          ck: Optional[_Checkpoint], rc0,
                          init_mv_adjust: int) -> SolveResult:
    """Ladder-exhausted last resort: continue on the host loop (whose
    ``halo`` carries its own per-exchange ladder) from the checkpoint,
    stitching the fused history prefix onto the host continuation."""
    from repro.solve import krylov

    host = krylov.cg if solver == "cg" else krylov.bicgstab
    if ck is None:
        res = host(op, b, tol=tol, maxiter=maxiter)
        base = res.status.split("+")[0]
        return dataclasses.replace(
            res, status=_finish_status(base + "+resume:0", res.restarts, op,
                                       rc0),
        )
    x0h = np.asarray(ck.vec)[:, 0, :]
    prefix = [float(h) for h in np.asarray(ck.hist)[0, :ck.k]]
    res = host(op, b, x0=x0h, tol=tol, maxiter=maxiter - ck.it)
    base = res.status.split("+")[0]
    return SolveResult(
        x=np.asarray(res.x),
        converged=res.converged,
        iterations=ck.it + res.iterations,
        residuals=tuple(prefix + list(res.residuals[1:])),
        matvecs=ck.mvc - init_mv_adjust + res.matvecs,
        status=_finish_status(base + "+resume:1", res.restarts, op, rc0),
        restarts=res.restarts,
    )


def fused_cg(op, b, x0=None, tol: float = 1e-6, maxiter: int = 500,
             reductions=None,
             checkpoint_every: Optional[int] = None) -> SolveResult:
    """Whole-solve CG: one jitted ``lax.while_loop`` per solve.

    Drop-in for :func:`repro.solve.krylov.cg` (same contract, same
    ``SolveResult`` fields); ``op`` may be either executor flavor.  The
    compiled program is cached per (pattern, strategy, codec, overlap,
    kernel flavor, dtype, maxiter) -- see ``repro.comm.cache_stats()``.
    ``reductions`` only contributes its inter-pod compressor (the
    hierarchical tree itself is traced inline); pass the
    :class:`~repro.solve.reductions.DeviceReductions` you would hand the
    host loop.

    ``checkpoint_every=N`` arms fault tolerance: the loop carries a
    solver-state snapshot refreshed every N clean iterations, and an
    ``ExchangeIntegrityError`` surfaced by a ``verify=True`` operator is
    recovered host-side -- the ladder re-runs the fused program from the
    checkpoint on a healthy (strategy, codec), falling back to the host
    loop -- losing at most N iterations (``status`` gains ``+resume:<n>``).
    Fault-free solves behave identically either way.
    """
    return _fused_solve(op, b, x0, tol, maxiter, reductions, "cg",
                        checkpoint_every)


def fused_bicgstab(op, b, x0=None, tol: float = 1e-6, maxiter: int = 500,
                   reductions=None,
                   checkpoint_every: Optional[int] = None) -> SolveResult:
    """Whole-solve BiCGStab: one jitted ``lax.while_loop`` per solve.

    Drop-in for :func:`repro.solve.krylov.bicgstab`; see :func:`fused_cg`
    (including ``checkpoint_every`` checkpoint/resume fault tolerance).
    """
    return _fused_solve(op, b, x0, tol, maxiter, reductions, "bicgstab",
                        checkpoint_every)


FUSED_SOLVERS = {"cg": fused_cg, "bicgstab": fused_bicgstab}
