"""Execute node-aware strategy stage programs on a device mesh.

:class:`IrregularExchange` takes an :class:`~repro.comm.exchange.ExchangePattern`
and a strategy name, plans the static stage program (setup time, like the
paper's Algorithm 1 / communicator construction), fuses it
(:mod:`repro.comm.fusion`), and exposes a jitted ``shard_map`` callable that
performs the exchange:

    ``local [nranks, L]       ->  canonical recv buffer [nranks, H]``
    ``local [nranks, L, k...] ->  [nranks, H, k...]``  (batched payloads:
    multi-vector SpMM columns, per-token feature dims for MoE routing)

The executor mirrors :func:`repro.comm.exchange.simulate_stage` exactly; the
symbolic simulator is the oracle for the data movement, and
``ExchangePattern.reference`` is the oracle for the delivered values.

Setup cost is amortized twice over:

* **ext-once execution** -- at compile time every stage's indices are
  re-based onto a single ``[local | buf]`` scratch array allocated once per
  call, so no stage re-concatenates ``[buf, local]``.
* **plan/compile caches** -- module-level LRU caches keyed by
  ``(pattern fingerprint, strategy, message_cap, elem_bytes, fused)`` (plans)
  plus the mesh identity (executors).  Repeated ``IrregularExchange``
  constructions for the same exchange (every SpMV / MoE step) reuse the
  planned program and the jitted callable; per-``(dtype, payload shape)``
  specializations live in ``jax.jit``'s trace cache under that callable.
  Inspect with :func:`cache_stats`, reset with :func:`clear_caches`.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm import compression
from repro.comm import faults as faults_mod
from repro.comm import wire as wire_mod
from repro.comm.exchange import (
    A2ALocal,
    A2APod,
    ExchangePattern,
    Gather,
    LoweredProgram,
    PermuteWorld,
    SplitPhase,
    StagePlan,
    lower_program,
    plan,
    rebase_indices,
    split_phase,
)
from repro.comm.fusion import fuse
from repro.comm.topology import (
    LOCAL_AXIS,
    POD_AXIS,
    WORLD_AXES,
    PodTopology,
    make_exchange_mesh,
    shard_ranks,
)
from repro.trace import scope, span

# ---------------------------------------------------------------------------
# Compiled-program representation (ext-once execution)
# ---------------------------------------------------------------------------


#: kept as the module-local spelling of the lowering the executor was built
#: around; the canonical implementation now lives with the stage dataclasses
#: (:func:`repro.comm.exchange.lower_program`)
_rebase = rebase_indices


def _compile_program(sp: StagePlan) -> Tuple[Tuple, Tuple[np.ndarray, ...], int]:
    """Lower a stage program to executor ops + re-based index arrays.

    Back-compat tuple view of :func:`repro.comm.exchange.lower_program`:
    returns ``(ops, arrays, W_max)`` where every index array addresses the
    ``[local | buf]`` scratch of width ``L + W_max`` directly.
    """
    lp = lower_program(sp)
    return lp.ops, lp.arrays, lp.w_max


def _encode_blocks(blocks, codec: str):
    """Encode leading-axis wire blocks for an inter-pod collective.

    Returns ``(payload, aux)`` where ``aux`` is the per-block float32 scale
    for the int8 codec (shipped through the same collective) or ``None``.
    Only called when :func:`repro.comm.wire.applies` said yes.
    """
    if codec in ("bf16", "f16"):
        # saturate finite overflow only; true inf/nan propagate through the
        # cast (mirrors wire.roundtrip_np)
        wdt = jnp.bfloat16 if codec == "bf16" else jnp.float16
        fmax = float(jnp.finfo(wdt).max)
        sat = jnp.where(
            jnp.isfinite(blocks), jnp.clip(blocks, -fmax, fmax), blocks
        )
        return sat.astype(wdt), None
    # int8: one scale per leading-axis block, shared quantizer core
    # (finite-aware scale + reserved-code non-finite handling live in
    # repro.comm.compression; wire.roundtrip_np is the numpy oracle)
    f = blocks.astype(jnp.float32)
    amax = compression.finite_amax(f, axis=tuple(range(1, f.ndim)))
    scale = compression.int8_scale(amax, wire_mod.QMAX)
    bshape = (-1,) + (1,) * (f.ndim - 1)
    q = compression.int8_quantize(
        f, scale.reshape(bshape), wire_mod.QMAX, nonfinite_code=wire_mod.INT8_NONFINITE
    )
    return q, scale


def _decode_blocks(payload, aux, dtype):
    """Inverse of :func:`_encode_blocks` after the collective moved it."""
    if aux is None:
        return payload.astype(dtype)
    return compression.int8_dequantize(
        payload,
        aux.reshape((-1,) + (1,) * (payload.ndim - 1)),
        nonfinite_code=wire_mod.INT8_NONFINITE,
    ).astype(dtype)


def _wire_check(x, axes):
    """Device twin of :func:`repro.comm.faults.block_check_np`: the
    ``(sum |finite x|, nonfinite count, finite amax)`` triple per wire
    block, stacked on a trailing axis (``[..., 3]`` float32)."""
    f = x.astype(jnp.float32)
    finite = jnp.isfinite(f)
    mag = jnp.where(finite, jnp.abs(f), jnp.float32(0.0))
    s = jnp.sum(mag, axis=axes)
    c = jnp.sum((~finite).astype(jnp.float32), axis=axes)
    a = jnp.max(mag, axis=axes, initial=0.0)
    return jnp.stack([s, c, a], axis=-1)


def _check_violation(chk_pre, chk_moved_post, nelem: int, codec: str, encoded: bool):
    """Device twin of :func:`repro.comm.faults.check_violation`, reduced to
    one scalar per hop (the max violation over this shard's blocks)."""
    s0, c0, a0 = chk_pre[..., 0], chk_pre[..., 1], chk_pre[..., 2]
    s1, c1 = chk_moved_post[..., 0], chk_moved_post[..., 1]
    tol = faults_mod.sum_tolerance(codec, nelem, a0, s0, encoded)
    drift = jnp.abs(s1 - s0) - tol
    viol = jnp.where(c1 != c0, jnp.float32(jnp.inf), drift.astype(jnp.float32))
    return jnp.max(viol) if viol.ndim else viol


def _apply_injection(x, mask, kind: str, value: float):
    """Device twin of :func:`repro.comm.faults.apply_injection_np`."""
    m = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
    if kind == "zero":
        return jnp.where(m, jnp.zeros((), x.dtype), x)
    if kind == "corrupt":
        return jnp.where(m, jnp.asarray(value, x.dtype), x)
    if kind == "perturb":
        return jnp.where(m, x * jnp.asarray(value, x.dtype), x)
    raise ValueError(f"unknown injection kind {kind!r}")


def _execute(
    ops,
    topo: PodTopology,
    L: int,
    w_max: int,
    out_size: int,
    local,
    plan_arrays,
    codec: str = "none",
    verify: bool = False,
    fault_ops: Optional[Dict] = None,
):
    """Ops interpreter; runs inside shard_map.  ``local`` is ``[1, L, *feat]``.

    The scratch ``ext = [local | buf]`` is built with ONE fused pad per call
    (no zeros buffer is materialized); stages read/write the buf region in
    place instead of re-concatenating ``[buf, local]`` per round.

    ``codec`` is the inter-pod wire format (:mod:`repro.comm.wire`): the
    payload of an ``A2APod`` (off-diagonal blocks) or an inter-pod
    ``PermuteWorld`` round is encoded right before the collective and
    decoded right after it.  On-pod hops and the ``"none"`` codec run the
    exact full-precision ops -- bitwise identical to the codec-free
    executor.

    ``verify`` ships the :func:`_wire_check` triple of every inter-pod
    payload through the same collective and recomputes it after
    decode+injection; the per-hop max violations are returned alongside the
    output.  ``fault_ops`` maps ``(op index, permute round | None)`` to
    ``(kind, dev_mask, value)`` injections (compiled by
    :func:`repro.comm.faults.compile_faults`); each mask is indexed by this
    shard's world rank and applied to the decoded receive blocks, mirroring
    :func:`repro.comm.exchange.execute_numpy` bitwise.

    Each op runs under the device scope ``exchange.<kind>`` (``gather``,
    ``a2a_local``, ``a2a_pod``, ``permute``), the codec's encode and decode
    under ``exchange.codec``; callers wrap the whole in ``exchange``.

    Returns ``(out [1, out_size, *feat], viols)`` where ``viols`` is a list
    of per-hop violation scalars (empty unless ``verify``).
    """
    x = local[0]
    feat = x.shape[1:]
    ext = jnp.pad(x, ((0, w_max),) + ((0, 0),) * len(feat))
    encode = codec != "none" and wire_mod.applies(codec, x.dtype)
    viols = []
    rank = None
    if fault_ops:
        rank = jax.lax.axis_index(POD_AXIS) * topo.ppn + jax.lax.axis_index(LOCAL_AXIS)
    ai = 0
    for op_i, op in enumerate(ops):
        kind = op[0]
        with scope("exchange." + kind):
            if kind == "gather":
                _, width = op
                idx = plan_arrays[ai][0]
                ai += 1
                vals = ext.at[idx].get(mode="fill", fill_value=0)
                ext = ext.at[L : L + width].set(vals)
            elif kind in ("a2a_local", "a2a_pod"):
                _, buflen, has_idx = op
                if has_idx:
                    idx = plan_arrays[ai][0]
                    ai += 1
                    seg = ext.at[idx].get(mode="fill", fill_value=0)
                else:
                    seg = ext[L : L + buflen]
                groups, axis = (
                    (topo.ppn, LOCAL_AXIS)
                    if kind == "a2a_local"
                    else (topo.npods, POD_AXIS)
                )
                blocks = seg.reshape((groups, buflen // groups) + feat)
                check = verify and kind == "a2a_pod"
                if check:
                    chk = _wire_check(blocks, tuple(range(1, blocks.ndim)))
                    chk_moved = jax.lax.all_to_all(chk, axis, 0, 0, tiled=True)
                if kind == "a2a_pod" and encode:
                    with scope("exchange.codec"):
                        payload, aux = _encode_blocks(blocks, codec)
                    moved = jax.lax.all_to_all(payload, axis, 0, 0, tiled=True)
                    if aux is not None:
                        aux = jax.lax.all_to_all(aux, axis, 0, 0, tiled=True)
                    with scope("exchange.codec"):
                        res = _decode_blocks(moved, aux, x.dtype)
                    # the own-pod block never crossed DCI: the all_to_all self
                    # slot holds this rank's own send block, so restore it at
                    # full precision
                    me = jax.lax.axis_index(axis)
                    keep = (jnp.arange(groups) == me).reshape(
                        (groups,) + (1,) * (blocks.ndim - 1)
                    )
                    res = jnp.where(keep, blocks, res)
                else:
                    res = jax.lax.all_to_all(blocks, axis, 0, 0, tiled=True)
                if kind == "a2a_pod" and fault_ops:
                    for fkind, mask, value in fault_ops.get((op_i, None), ()):
                        res = _apply_injection(res, mask[rank], fkind, value)
                if check:
                    chk_post = _wire_check(res, tuple(range(1, res.ndim)))
                    nelem = int(np.prod(blocks.shape[1:], dtype=np.int64))
                    viols.append(
                        _check_violation(chk_moved, chk_post, nelem, codec, encode)
                    )
                ext = ext.at[L : L + buflen].set(res.reshape((buflen,) + feat))
            elif kind == "permute":
                _, rounds, blks, inters = op
                parts = []
                for ri, (perm, blk, inter) in enumerate(zip(rounds, blks, inters)):
                    sel = plan_arrays[ai][0]
                    ai += 1
                    send = ext.at[sel].get(mode="fill", fill_value=0)
                    if not perm:
                        parts.append(jnp.zeros_like(send))
                        continue
                    check = verify and inter
                    if check:
                        chk = _wire_check(send, tuple(range(send.ndim)))
                        chk_moved = jax.lax.ppermute(chk, WORLD_AXES, list(perm))
                    if inter and encode:
                        with scope("exchange.codec"):
                            payload, aux = _encode_blocks(send[None], codec)
                        moved = jax.lax.ppermute(payload[0], WORLD_AXES, list(perm))
                        if aux is not None:
                            aux = jax.lax.ppermute(aux[0], WORLD_AXES, list(perm))
                            aux = aux[None]
                        with scope("exchange.codec"):
                            part = _decode_blocks(moved[None], aux, x.dtype)[0]
                    else:
                        part = jax.lax.ppermute(send, WORLD_AXES, list(perm))
                    if fault_ops:
                        for fkind, mask, value in fault_ops.get((op_i, ri), ()):
                            part = _apply_injection(part, mask[rank], fkind, value)
                    if check:
                        chk_post = _wire_check(part, tuple(range(part.ndim)))
                        nelem = int(np.prod(send.shape, dtype=np.int64))
                        viols.append(
                            _check_violation(
                                chk_moved, chk_post, nelem, codec, inter and encode
                            )
                        )
                    parts.append(part)
                width = sum(blks)
                if parts:
                    ext = ext.at[L : L + width].set(jnp.concatenate(parts))
            else:
                raise TypeError(f"unknown op {op!r}")
    return ext[L : L + out_size][None], viols


# ---------------------------------------------------------------------------
# Traceable exchange programs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceableExchange:
    """A planned exchange as a first-class traceable program value.

    The pair the whole-solve path closes over inside ``jit``: a pytree of
    plan arrays (:attr:`plan_arrays`, one ``[nranks, ...]`` int32 array per
    lowered index table -- fed through ``shard_map`` input specs like any
    payload) plus the pure per-shard callable :meth:`run`.  Everything else
    on the instance is static Python data (opcodes, topology, codec,
    integrity-check metadata) that traces into the program as constants, so
    a ``TraceableExchange`` can sit inside a ``lax.while_loop`` body, a
    scanned pipeline stage, or the barrier executor alike -- the jitted
    executor of :class:`IrregularExchange` is now just ``jax.shard_map(run)``.

    Build one with :func:`traceable_exchange` (or
    :meth:`IrregularExchange.traceable`).

    ``verify=True`` programs expose :meth:`run_verified`, which additionally
    returns the per-DCI-hop max-violation vector (``[n_checks]`` float32, in
    :attr:`checks` order) computed by the same wire integrity checks as the
    host path; callers surface positives as
    :class:`repro.comm.faults.ExchangeIntegrityError` via :meth:`raise_viols`.
    """

    lowered: LoweredProgram
    topo: PodTopology
    strategy: str
    codec: str = "none"
    #: integrity-check metadata: ``checks[j] = (ordinal, op_index,
    #: stage_kind, round_index)`` names the DCI hop behind violation column j
    checks: Tuple[tuple, ...] = ()
    #: True when :meth:`run_verified` emits a violation vector (verify was
    #: requested AND the plan has DCI-crossing hops)
    emit_checks: bool = False
    #: compiled fault injections keyed ``(op_index, round_index)`` (static:
    #: baked into the trace; a fused loop applies them on every iteration)
    fault_ops: Optional[Dict] = None
    delay_s: float = 0.0
    #: device copies of ``lowered.arrays`` -- THE plan-array pytree
    plan_arrays: Tuple[jax.Array, ...] = ()

    @property
    def out_size(self) -> int:
        return self.lowered.out_size

    @property
    def local_size(self) -> int:
        return self.lowered.local_size

    def run(self, local, *plan_arrays):
        """Pure per-shard exchange: ``local [1, L, *feat] -> [1, H, *feat]``.

        Runs inside ``shard_map`` (directly or nested in a traced loop);
        ``plan_arrays`` are the per-shard slices of :attr:`plan_arrays`.
        """
        with scope("exchange"):
            out, _ = _execute(
                self.lowered.ops, self.topo, self.lowered.local_size,
                self.lowered.w_max, self.lowered.out_size, local, plan_arrays,
                self.codec, verify=False, fault_ops=self.fault_ops,
            )
        return out

    def run_verified(self, local, *plan_arrays):
        """Like :meth:`run` but returns ``(out, viols [n_checks] f32)``.

        With :attr:`emit_checks` False the violation vector is empty.
        """
        with scope("exchange"):
            out, viols = _execute(
                self.lowered.ops, self.topo, self.lowered.local_size,
                self.lowered.w_max, self.lowered.out_size, local, plan_arrays,
                self.codec, verify=self.emit_checks, fault_ops=self.fault_ops,
            )
        if viols:
            return out, jnp.stack(viols)
        return out, jnp.zeros((0,), jnp.float32)

    def raise_viols(self, viols: np.ndarray) -> None:
        """Raise :class:`~repro.comm.faults.ExchangeIntegrityError` for the
        first positive column of a gathered ``[..., n_checks]`` violation
        array -- the same structured fields as the host executor's raise."""
        viols = np.asarray(viols).reshape(-1, len(self.checks))
        bad = (viols > 0.0).any(axis=0)
        if not bad.any():
            return
        j = int(np.argmax(bad))
        _, op_index, stage_kind, round_index = self.checks[j]
        raise faults_mod.ExchangeIntegrityError(
            strategy=self.strategy,
            codec=self.codec,
            stage_kind=stage_kind,
            op_index=op_index,
            round_index=round_index,
            violation=float(viols[:, j].max()),
        )


def traceable_exchange(
    sp: StagePlan,
    mesh: jax.sharding.Mesh,
    codec: str = "none",
    verify: bool = False,
    faults: Optional[faults_mod.FaultPlan] = None,
) -> TraceableExchange:
    """Lower a planned stage program to its traceable program value.

    The plan arrays are placed on ``mesh``, one rank's slice per device.

    This is the programmatic form of what :func:`_executor` wraps in
    ``shard_map`` for the barrier path; fused consumers
    (:mod:`repro.solve.fused`) embed :meth:`TraceableExchange.run` directly
    inside their own traced loops instead.
    """
    lp = lower_program(sp)
    checks = tuple(
        (ordinal, op_index, stage_kind, round_index)
        for ordinal, op_index, stage_kind, round_index, _, _ in (
            faults_mod.iter_inter_hops(sp)
        )
    )
    fault_ops: Optional[Dict] = None
    delay_s = 0.0
    if faults is not None:
        cf = faults_mod.compile_faults(sp, codec, faults)
        delay_s = cf.delay_s
        grouped: Dict[tuple, list] = {}
        for inj in cf.injections:
            grouped.setdefault((inj.op_index, inj.round_index), []).append(
                (inj.kind, jnp.asarray(inj.dev_mask), inj.value)
            )
        fault_ops = {k: tuple(v) for k, v in grouped.items()} or None
    return TraceableExchange(
        lowered=lp,
        topo=sp.pattern.topo,
        strategy=sp.strategy,
        codec=codec,
        checks=checks,
        emit_checks=verify and bool(checks),
        fault_ops=fault_ops,
        delay_s=delay_s,
        plan_arrays=tuple(shard_ranks(a, mesh) for a in lp.arrays),
    )


# ---------------------------------------------------------------------------
# Plan / executor caches
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    plan_hits: int = 0
    plan_misses: int = 0
    exec_hits: int = 0
    exec_misses: int = 0
    #: local-compute compile cache (repro.sparse.spmv SpMV/SpMM programs,
    #: keyed by (pattern fingerprint, payload width k, ...))
    compute_hits: int = 0
    compute_misses: int = 0
    #: split-phase decomposition + jitted-merge cache (``_SPLIT_CACHE``,
    #: keyed by pattern fingerprint; populated by ``IrregularExchange.start``
    #: and the solver's overlapped numpy executor)
    split_hits: int = 0
    split_misses: int = 0
    #: whole-instance front door used by per-batch pattern producers
    #: (:func:`exchange_for`); a hit means zero planning work for the batch
    exchange_hits: int = 0
    exchange_misses: int = 0
    #: fused whole-solve programs (``_FUSED_CACHE``: one jitted
    #: ``lax.while_loop`` Krylov solve per (pattern, strategy, codec, dtype,
    #: ...); populated by :mod:`repro.solve.fused`).  A miss is a whole-solve
    #: retrace, so this is the costliest cache to thrash.
    fused_hits: int = 0
    fused_misses: int = 0
    #: LRU evictions per cache -- the serving layer's memory-pressure signal
    #: (a multi-tenant fingerprint universe larger than the cache capacity
    #: shows up here, not as silent recompiles).  Consistency invariant for
    #: any cache whose capacity never shrank mid-run:
    #: ``evictions == misses - live_entries`` (see :func:`cache_sizes`).
    plan_evictions: int = 0
    exec_evictions: int = 0
    split_evictions: int = 0
    exchange_evictions: int = 0
    compute_evictions: int = 0
    fused_evictions: int = 0


_stats = CacheStats()
_PLAN_CACHE: "OrderedDict[tuple, StagePlan]" = OrderedDict()
_EXEC_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_MESH_CACHE: "OrderedDict[tuple, jax.sharding.Mesh]" = OrderedDict()
#: split-phase decompositions + jitted merge fns, keyed by pattern fingerprint
_SPLIT_CACHE: "OrderedDict[str, tuple]" = OrderedDict()
#: constructed IrregularExchange instances (per-batch dynamic-pattern callers)
_EXCHANGE_CACHE: "OrderedDict[tuple, IrregularExchange]" = OrderedDict()
#: fused whole-solve programs (jitted fns), keyed by
#: (fingerprint, solver, strategy, codec, overlap, dtype, ...) tuples built
#: by repro.solve.fused
_FUSED_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
#: external LRUs (e.g. the SpMM compute cache) reset by clear_caches()
_EXTERNAL_CACHES: List[OrderedDict] = []
PLAN_CACHE_MAX = 256
EXEC_CACHE_MAX = 64
EXCHANGE_CACHE_MAX = 64
FUSED_CACHE_MAX = 32


def cache_stats() -> CacheStats:
    """Snapshot of plan/executor/compute cache hit counters."""
    return dataclasses.replace(_stats)


def cache_sizes() -> Dict[str, int]:
    """Live entry counts per module cache (the denominator the eviction
    counters are consistent against; see :class:`CacheStats`)."""
    return {
        "plan": len(_PLAN_CACHE),
        "exec": len(_EXEC_CACHE),
        "split": len(_SPLIT_CACHE),
        "exchange": len(_EXCHANGE_CACHE),
        "fused": len(_FUSED_CACHE),
        "external": sum(len(c) for c in _EXTERNAL_CACHES),
    }


def set_cache_limits(
    plan: Optional[int] = None,
    exec_: Optional[int] = None,
    exchange: Optional[int] = None,
    fused: Optional[int] = None,
) -> Dict[str, int]:
    """Resize the module LRU capacities, trimming oldest-first immediately.

    The serving layer's memory budget maps onto these caps: a multi-tenant
    front-end that must bound resident plan/executor state calls this with
    its budget-derived entry counts, and the trims land in the eviction
    counters like any organic pressure.  ``None`` leaves a cap unchanged;
    the split-phase cache shares ``plan``'s cap by design (one decomposition
    per resident pattern).  Returns the caps now in force.
    """
    global PLAN_CACHE_MAX, EXEC_CACHE_MAX, EXCHANGE_CACHE_MAX, FUSED_CACHE_MAX
    for name, value in (
        ("plan", plan),
        ("exec_", exec_),
        ("exchange", exchange),
        ("fused", fused),
    ):
        if value is not None and value < 1:
            raise ValueError(f"{name} cache limit must be >= 1, got {value}")
    if plan is not None:
        PLAN_CACHE_MAX = plan
        _trim(_PLAN_CACHE, plan, "plan_evictions")
        _trim(_SPLIT_CACHE, plan, "split_evictions")
    if exec_ is not None:
        EXEC_CACHE_MAX = exec_
        _trim(_EXEC_CACHE, exec_, "exec_evictions")
    if exchange is not None:
        EXCHANGE_CACHE_MAX = exchange
        _trim(_EXCHANGE_CACHE, exchange, "exchange_evictions")
    if fused is not None:
        FUSED_CACHE_MAX = fused
        _trim(_FUSED_CACHE, fused, "fused_evictions")
    return {
        "plan": PLAN_CACHE_MAX,
        "exec": EXEC_CACHE_MAX,
        "exchange": EXCHANGE_CACHE_MAX,
        "fused": FUSED_CACHE_MAX,
    }


def register_cache(cache: OrderedDict) -> None:
    """Register an external LRU so :func:`clear_caches` resets it too."""
    # identity, not equality: two distinct empty OrderedDicts compare ==
    if not any(c is cache for c in _EXTERNAL_CACHES):
        _EXTERNAL_CACHES.append(cache)


def clear_caches() -> None:
    _PLAN_CACHE.clear()
    _EXEC_CACHE.clear()
    _MESH_CACHE.clear()
    _SPLIT_CACHE.clear()
    _EXCHANGE_CACHE.clear()
    _FUSED_CACHE.clear()
    for cache in _EXTERNAL_CACHES:
        cache.clear()
    _stats.plan_hits = _stats.plan_misses = 0
    _stats.exec_hits = _stats.exec_misses = 0
    _stats.compute_hits = _stats.compute_misses = 0
    _stats.split_hits = _stats.split_misses = 0
    _stats.exchange_hits = _stats.exchange_misses = 0
    _stats.fused_hits = _stats.fused_misses = 0
    _stats.plan_evictions = _stats.exec_evictions = 0
    _stats.split_evictions = _stats.exchange_evictions = 0
    _stats.compute_evictions = _stats.fused_evictions = 0


def _trim(cache: OrderedDict, max_size: int, evict_stat: Optional[str]) -> None:
    while len(cache) > max_size:
        cache.popitem(last=False)
        if evict_stat is not None:
            setattr(_stats, evict_stat, getattr(_stats, evict_stat) + 1)


def _lru_get(
    cache: OrderedDict, key, max_size: int, build, evict_stat: Optional[str] = None
):
    if key in cache:
        cache.move_to_end(key)
        return cache[key], True
    val = build()
    cache[key] = val
    _trim(cache, max_size, evict_stat)
    return val, False


def compute_cached(cache: OrderedDict, key, max_size: int, build):
    """LRU get for a registered local-compute compile cache, with the hit /
    miss accounted under ``compute_hits`` / ``compute_misses``."""
    val, hit = _lru_get(cache, key, max_size, build, "compute_evictions")
    if hit:
        _stats.compute_hits += 1
    else:
        _stats.compute_misses += 1
    return val


def fused_cached(key, build):
    """LRU get for the fused whole-solve program cache.

    ``build()`` returns the cached value (the jitted solve program; each
    caller passes its own operands); hits and misses land under
    ``fused_hits`` / ``fused_misses`` and trims under ``fused_evictions``,
    so fused programs participate in the same cache-pressure machinery
    (:func:`cache_sizes`, :func:`set_cache_limits`) as every other compiled
    artifact.
    """
    val, hit = _lru_get(_FUSED_CACHE, key, FUSED_CACHE_MAX, build, "fused_evictions")
    if hit:
        _stats.fused_hits += 1
    else:
        _stats.fused_misses += 1
    return val


def _plan_key(
    pattern: ExchangePattern,
    strategy: str,
    message_cap_bytes: int,
    elem_bytes: int,
    fuse_program: bool,
) -> tuple:
    return (
        pattern.fingerprint(),
        strategy,
        message_cap_bytes,
        elem_bytes,
        fuse_program,
    )


def planned(
    pattern: ExchangePattern,
    strategy: str,
    message_cap_bytes: int = 16384,
    elem_bytes: int = 4,
    fuse_program: bool = True,
    _key: Optional[tuple] = None,
) -> StagePlan:
    """Plan (and optionally fuse) with module-level memoization."""
    key = _key or _plan_key(
        pattern, strategy, message_cap_bytes, elem_bytes, fuse_program
    )

    def build():
        sp = plan(
            strategy,
            pattern,
            message_cap_bytes=message_cap_bytes,
            elem_bytes=elem_bytes,
        )
        return fuse(sp) if fuse_program else sp

    sp, hit = _lru_get(_PLAN_CACHE, key, PLAN_CACHE_MAX, build, "plan_evictions")
    if hit:
        _stats.plan_hits += 1
    else:
        _stats.plan_misses += 1
    return sp


def _default_mesh(topo: PodTopology) -> jax.sharding.Mesh:
    key = (topo.npods, topo.ppn)
    mesh, _ = _lru_get(_MESH_CACHE, key, 16, lambda: make_exchange_mesh(topo))
    return mesh


def _mesh_key(mesh: jax.sharding.Mesh) -> tuple:
    return (
        tuple(int(d.id) for d in mesh.devices.flat),
        tuple(mesh.devices.shape),
        tuple(mesh.axis_names),
    )


@dataclasses.dataclass(frozen=True)
class _ExecMeta:
    """Sidecar of a built executor: verify-output layout + injected delay.

    ``checks[j] = (hop ordinal, op_index, stage_kind, round_index)`` names
    the DCI hop behind column ``j`` of the executor's violation output.
    """

    emit_checks: bool
    checks: Tuple[tuple, ...]
    delay_s: float


def _executor(
    sp: StagePlan,
    plan_key: tuple,
    mesh: jax.sharding.Mesh,
    codec: str = "none",
    verify: bool = False,
    faults=None,
):
    """Build (or fetch) the jitted executor for one plan/codec/mesh.

    Returns ``(fn, arrays, meta)`` where ``meta`` is an :class:`_ExecMeta`.
    With ``verify`` on and the plan containing inter-pod hops, ``fn``
    returns ``(out, viols [nranks, n_checks])`` -- one max-violation scalar
    per DCI hop, in the program order of ``meta.checks``.  ``faults`` bakes
    a compiled :class:`repro.comm.faults.FaultPlan`'s injection masks into
    the traced program (the per-call active gating is the caller's job: it
    picks this executor or the fault-free twin per call).
    """
    fp = faults.fingerprint() if faults is not None else None
    key = plan_key + (codec, verify, fp) + _mesh_key(mesh)

    def build():
        # the barrier executor is now just shard_map over the traceable
        # program value; fused consumers embed tx.run in their own loops
        tx = traceable_exchange(sp, codec=codec, verify=verify, faults=faults,
                                mesh=mesh)
        emit = tx.emit_checks
        specs = (P(WORLD_AXES),) * (1 + len(tx.plan_arrays))
        out_specs = (P(WORLD_AXES), P(WORLD_AXES)) if emit else P(WORLD_AXES)

        if emit:

            def run(local, *plan_arrays):
                out, viols = tx.run_verified(local, *plan_arrays)
                return out, viols[None]

        else:
            run = tx.run

        fn = jax.jit(
            jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=out_specs)
        )
        meta = _ExecMeta(emit_checks=emit, checks=tx.checks, delay_s=tx.delay_s)
        return fn, tx.plan_arrays, meta

    val, hit = _lru_get(_EXEC_CACHE, key, EXEC_CACHE_MAX, build, "exec_evictions")
    if hit:
        _stats.exec_hits += 1
    else:
        _stats.exec_misses += 1
    return val


# ---------------------------------------------------------------------------
# Split-phase merge
# ---------------------------------------------------------------------------


def merge_shard(mask, valid, li, ri, local_out, remote_out):
    """Split-phase merge of one shard: assemble the canonical ``[1, H, ...]``
    recv buffer from the on-pod and inter-pod phase outputs by per-rank
    gathers (no communication)."""
    nfeat = local_out.ndim - 2

    def take(buf, idx):
        idx = jnp.minimum(idx, buf.shape[1] - 1)
        idx = idx.reshape(idx.shape + (1,) * nfeat)
        idx = jnp.broadcast_to(idx, idx.shape[:2] + buf.shape[2:])
        return jnp.take_along_axis(buf, idx, axis=1)

    with scope("exchange"):
        m = mask.reshape(mask.shape + (1,) * nfeat)
        v = valid.reshape(valid.shape + (1,) * nfeat)
        lo = take(local_out, li)
        merged = jnp.where(m, lo, take(remote_out, ri))
        return jnp.where(v, merged, jnp.zeros_like(lo))


def _build_merge(sp: SplitPhase, mesh: jax.sharding.Mesh):
    """Jitted :func:`merge_shard` over ``mesh``; its index maps are placed
    on the mesh once and passed as arguments."""
    maps = tuple(
        shard_ranks(a, mesh)
        for a in (sp.from_local, sp.valid, sp.local_idx, sp.remote_idx)
    )
    merge = jax.jit(
        jax.shard_map(
            merge_shard, mesh=mesh, in_specs=(P(WORLD_AXES),) * 6,
            out_specs=P(WORLD_AXES),
        )
    )
    return lambda local_out, remote_out: merge(*maps, local_out, remote_out)


class _LazyMerge:
    """Builds the jitted split-phase merge on first call, once per mesh.

    Laziness matters because the jax-free consumers of the split cache
    (:class:`repro.solve.operator.NumpySpMV`) only need the decomposition;
    eagerly constructing the merge would transfer its index maps to device
    for a function they never invoke.
    """

    __slots__ = ("_sp", "_fns")

    def __init__(self, sp: SplitPhase):
        self._sp = sp
        self._fns: Dict[jax.sharding.Mesh, object] = {}

    def __call__(self, local_out, remote_out):
        mesh = local_out.sharding.mesh
        fn = self._fns.get(mesh)
        if fn is None:
            fn = self._fns[mesh] = _build_merge(self._sp, mesh)
        return fn(local_out, remote_out)


def _split_phase_cached(pattern: ExchangePattern) -> tuple:
    key = pattern.fingerprint()

    def build():
        sp = split_phase(pattern)
        return sp, _LazyMerge(sp)

    val, hit = _lru_get(_SPLIT_CACHE, key, PLAN_CACHE_MAX, build, "split_evictions")
    if hit:
        _stats.split_hits += 1
    else:
        _stats.split_misses += 1
    return val


@dataclasses.dataclass
class ExchangeHandle:
    """An in-flight two-phase exchange (see :meth:`IrregularExchange.start`).

    ``local_halo`` is the on-pod phase result, available as soon as
    :meth:`IrregularExchange.start` returns; the inter-pod phase was
    dispatched first and completes asynchronously.  :meth:`finish` merges
    both phases into the full canonical recv buffer -- bit-identical to the
    barrier ``IrregularExchange.__call__``.
    """

    local_halo: jax.Array
    remote_halo: jax.Array
    _merge: object
    _done: Optional[jax.Array] = None

    def finish(self) -> jax.Array:
        """Block on the inter-pod phase and return ``[nranks, H, *feat]``."""
        if self._done is None:
            self._done = self._merge(self.local_halo, self.remote_halo)
        return self._done


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class IrregularExchange:
    """A planned, compiled irregular exchange for one strategy.

    Args:
      pattern: the element-level communication pattern.
      strategy: "standard" | "two_step" | "three_step" | "split".
      mesh: optional pre-built ``("pod", "local")`` mesh.
      message_cap_bytes: Split's user cap (Algorithm 1 input).
      elem_bytes: element width used for cap arithmetic / byte accounting.
      fuse_program: run the :mod:`repro.comm.fusion` rewrites (default on).
      wire: inter-pod wire codec, one of
        :data:`repro.comm.wire.WIRE_CODECS` (``"none"`` | ``"bf16"`` |
        ``"f16"`` | ``"int8"``).  Lossy codecs shrink only the DCI-crossing
        bytes -- on-pod hops and the destination's own-pod ``A2APod``
        blocks stay full precision -- with the per-element error bounds of
        :data:`repro.comm.wire.REL_ERROR_BOUND`; ``"none"`` is bitwise
        identical to the codec-free executor.  The plan is codec-independent
        (one plan per fingerprint); the jitted executor is cached per
        ``(plan, wire, mesh)``.

    Construction is cheap when an equal exchange was built before: the plan
    and the jitted executor come from module-level caches (see
    :func:`cache_stats`).

    Example (needs ``jax.device_count() >= pattern.topo.nranks``, e.g. via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)::

        import numpy as np
        from repro.comm import IrregularExchange, PodTopology, random_pattern

        topo = PodTopology(npods=2, ppn=4)
        pat = random_pattern(np.random.default_rng(0), topo, local_size=6)
        ex = IrregularExchange(pat, "two_step")

        local = np.ones((topo.nranks, 6), np.float32)
        halo = ex(local)                    # barrier: [nranks, H]

        handle = ex.start(local)            # split-phase (overlap) variant:
        fast = handle.local_halo            # on-pod data, ready immediately
        assert np.array_equal(np.asarray(handle.finish()), np.asarray(halo))
    """

    pattern: ExchangePattern
    strategy: str
    mesh: Optional[jax.sharding.Mesh] = None
    message_cap_bytes: int = 16384
    elem_bytes: int = 4
    fuse_program: bool = True
    wire: str = "none"
    #: opt-in wire integrity verification (repro.comm.faults check values);
    #: a failed check raises ExchangeIntegrityError and engages the
    #: retry -> codec-demotion -> strategy-re-advise recovery ladder
    verify: bool = False
    #: seeded deterministic fault injection (repro.comm.faults.FaultPlan)
    faults: Optional[faults_mod.FaultPlan] = None
    #: shared health tracker for the ladder / advisor / watchdog; created
    #: on demand when verify or faults are set
    health: Optional[faults_mod.HealthTracker] = None
    max_retries: int = 1
    fallback: bool = True

    def __post_init__(self) -> None:
        wire_mod.check_codec(self.wire)
        plan_key = _plan_key(
            self.pattern,
            self.strategy,
            self.message_cap_bytes,
            self.elem_bytes,
            self.fuse_program,
        )
        self.plan: StagePlan = planned(
            self.pattern,
            self.strategy,
            message_cap_bytes=self.message_cap_bytes,
            elem_bytes=self.elem_bytes,
            fuse_program=self.fuse_program,
            _key=plan_key,
        )
        if self.mesh is None:
            self.mesh = _default_mesh(self.pattern.topo)
        self._fn, self._arrays, self._meta = _executor(
            self.plan, plan_key, self.mesh, self.wire, verify=self.verify
        )
        if self.faults is not None:
            self._fn_faulty, _, self._meta_faulty = _executor(
                self.plan, plan_key, self.mesh, self.wire,
                verify=self.verify, faults=self.faults,
            )
        else:
            self._fn_faulty, self._meta_faulty = self._fn, self._meta
        if self.health is None and (self.verify or self.faults is not None):
            self.health = faults_mod.HealthTracker()
        self._two_phase: Optional[tuple] = None
        self._variants: Dict[tuple, "IrregularExchange"] = {}
        self._calls = 0
        self._traceable: Optional[TraceableExchange] = None
        #: RecoveryPath.key of the most recent recovered call, or None
        self.last_recovery: Optional[str] = None

    # ------------------------------------------------------------------
    def traceable(self) -> TraceableExchange:
        """This exchange as a traceable program value (built lazily, once).

        The returned :class:`TraceableExchange` carries the same plan,
        codec, verify and fault configuration as this instance, but as a
        pure per-shard callable + plan-array pytree that callers can close
        over inside their own jitted programs (the fused solver path).
        """
        if self._traceable is None:
            self._traceable = traceable_exchange(
                self.plan, codec=self.wire, verify=self.verify,
                faults=self.faults, mesh=self.mesh,
            )
        return self._traceable

    # ------------------------------------------------------------------
    def __call__(self, local: jax.Array) -> jax.Array:
        """``local [nranks, L, *feat] -> canonical recv [nranks, H, *feat]``.

        Trailing feature dims (multi-vector SpMM ``k``, per-token features)
        ride along under the same plan; jit specializes per trailing shape.

        With ``verify`` or ``faults`` configured, calls run through the
        recovery ladder (:func:`repro.comm.faults.run_ladder`): a failed
        integrity check is retried up to ``max_retries`` times, then the
        lossy codec is demoted to ``"none"``, then the strategy is
        re-advised with the offending hop marked degraded; the final
        failure re-raises :class:`repro.comm.faults.ExchangeIntegrityError`.
        The fault-free default path is the unchanged direct dispatch.
        """
        n, L = self.pattern.topo.nranks, self.pattern.local_size
        if local.ndim < 2 or local.shape[:2] != (n, L):
            raise ValueError(
                f"expected [{n}, {L}, *feat], got {tuple(local.shape)}"
            )
        with span("exchange"):
            if self.faults is None and not self.verify:
                return self._fn(local, *self._arrays)
            return self._guarded_call(local)

    # -- verification + recovery ---------------------------------------
    def _raw_call(self, local: jax.Array, call_index: int) -> jax.Array:
        """One physical attempt: pick the faulted or clean executor by the
        FaultPlan's call gating, surface check violations as errors."""
        active = self.faults is not None and self.faults.active(call_index)
        fn, meta = (
            (self._fn_faulty, self._meta_faulty) if active else (self._fn, self._meta)
        )
        out = fn(local, *self._arrays)
        if active and meta.delay_s > 0.0:
            time.sleep(meta.delay_s)  # the injected slow-hop latency
        if meta.emit_checks:
            out, viols = out
            self._raise_from_viols(np.asarray(viols), meta.checks)
        return out

    def _raise_from_viols(self, viols: np.ndarray, checks) -> None:
        bad = (viols > 0.0).any(axis=0)
        if not bad.any():
            return
        j = int(np.argmax(bad))
        _, op_index, stage_kind, round_index = checks[j]
        raise faults_mod.ExchangeIntegrityError(
            strategy=self.plan.strategy,
            codec=self.wire,
            stage_kind=stage_kind,
            op_index=op_index,
            round_index=round_index,
            violation=float(viols[:, j].max()),
        )

    def _variant(self, strategy: str, wire: str) -> "IrregularExchange":
        if strategy == self.strategy and wire == self.wire:
            return self
        key = (strategy, wire)
        v = self._variants.get(key)
        if v is None:
            v = IrregularExchange(
                self.pattern,
                strategy,
                mesh=self.mesh,
                message_cap_bytes=self.message_cap_bytes,
                elem_bytes=self.elem_bytes,
                fuse_program=self.fuse_program,
                wire=wire,
                verify=self.verify,
                faults=self.faults,
                health=self.health,
                max_retries=0,
                fallback=False,
            )
            self._variants[key] = v
        return v

    def _guarded_call(self, local: jax.Array) -> jax.Array:
        def attempt(strategy: str, wire: str):
            idx = self._calls
            self._calls += 1
            return self._variant(strategy, wire)._raw_call(local, idx)

        out, path = faults_mod.run_ladder(
            attempt,
            strategy=self.strategy,
            wire=self.wire,
            health=self.health,
            max_retries=self.max_retries,
            fallback=self.fallback,
            choose_alternative=faults_mod.advise_alternative(
                self.pattern, self.elem_bytes
            ),
        )
        if path is not None:
            self.last_recovery = path.key
        return out

    # ------------------------------------------------------------------
    def start(self, local: jax.Array) -> ExchangeHandle:
        """Begin a split-phase exchange; on-pod data is ready immediately.

        The pattern is factored (:func:`repro.comm.exchange.split_phase`)
        into an inter-pod sub-pattern -- planned with this exchange's
        strategy and dispatched *first*, so it is in flight while anything
        else runs -- and an on-pod sub-pattern delivered synchronously as
        ``handle.local_halo``.  Work that needs no halo data (the diag-block
        product of :class:`repro.sparse.spmv.DistributedSpMV`), or only the
        on-pod part of it (``handle.local_halo``), can execute between
        ``start()`` and ``handle.finish()``, hiding the inter-node latency
        behind it; ``finish()`` merges both phases into exactly the buffer
        :meth:`__call__` returns.

        Both sub-exchanges and the merge come from the module-level caches
        (and are memoized on the instance), so repeated ``start()`` calls
        replan nothing and re-hash nothing.
        """
        if self._two_phase is None:
            sp, merge = _split_phase_cached(self.pattern)
            self._two_phase = (
                # the inter-pod phase inherits this exchange's wire codec;
                # the on-pod phase is always full precision
                IrregularExchange(
                    sp.remote,
                    self.strategy,
                    mesh=self.mesh,
                    message_cap_bytes=self.message_cap_bytes,
                    elem_bytes=self.elem_bytes,
                    fuse_program=self.fuse_program,
                    wire=self.wire,
                    # faults only ever hit DCI-crossing segments, so the
                    # guard rails ride on the inter-pod phase alone
                    verify=self.verify,
                    faults=self.faults,
                    health=self.health,
                    max_retries=self.max_retries,
                    fallback=self.fallback,
                ),
                IrregularExchange(
                    sp.local,
                    "local",
                    mesh=self.mesh,
                    elem_bytes=self.elem_bytes,
                    fuse_program=self.fuse_program,
                ),
                merge,
            )
        remote_ex, local_ex, merge = self._two_phase
        remote = remote_ex(local)  # async dispatch: inter-pod phase in flight
        return ExchangeHandle(
            local_halo=local_ex(local), remote_halo=remote, _merge=merge
        )

    # ------------------------------------------------------------------
    def reference(self, local: np.ndarray) -> np.ndarray:
        return self.pattern.reference(local)

    @property
    def wire_bytes(self) -> Tuple[int, int]:
        """(intra-pod, inter-pod) bytes on the wire incl. padding.

        Inter-pod bytes are costed at the wire codec's element width (plus
        int8 scale side information); ``wire="none"`` reports the planner's
        accounting verbatim (:func:`repro.comm.wire.scaled_wire_bytes`).
        """
        return wire_mod.scaled_wire_bytes(self.plan, self.wire, self.elem_bytes)

    @property
    def payload_bytes(self) -> Tuple[int, int]:
        """(intra-pod, inter-pod) useful payload bytes."""
        return (self.plan.intra_pod_bytes, self.plan.inter_pod_bytes)


STRATEGY_NAMES = ("standard", "two_step", "three_step", "split")


def exchange_for(
    pattern: ExchangePattern,
    strategy: str,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    message_cap_bytes: int = 16384,
    elem_bytes: int = 4,
    wire: str = "none",
) -> IrregularExchange:
    """Memoized :class:`IrregularExchange` constructor for dynamic callers.

    Per-batch pattern producers (MoE routing) re-request an exchange every
    step; constructing a fresh instance each time is cheap-ish (plan and
    executor are already cached) but still re-runs ``__post_init__``
    bookkeeping.  This front-door LRU returns the *same* instance for an
    equal ``(fingerprint, strategy, caps, wire, mesh)`` request, so hot
    routing buckets cost one dict lookup.  Cleared by :func:`clear_caches`.
    """
    key = (
        pattern.fingerprint(),
        strategy,
        message_cap_bytes,
        elem_bytes,
        wire,
        _mesh_key(mesh) if mesh is not None else None,
    )

    def build():
        return IrregularExchange(
            pattern,
            strategy,
            mesh=mesh,
            message_cap_bytes=message_cap_bytes,
            elem_bytes=elem_bytes,
            wire=wire,
        )

    ex, hit = _lru_get(_EXCHANGE_CACHE, key, EXCHANGE_CACHE_MAX, build, "exchange_evictions")
    if hit:
        _stats.exchange_hits += 1
    else:
        _stats.exchange_misses += 1
    return ex
