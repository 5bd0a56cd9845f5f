"""Mixture-of-Experts with expert-parallel all-to-all dispatch.

Token -> expert routing is the modern LM incarnation of the paper's irregular
point-to-point pattern: per-step, every data shard sends a data-dependent
subset of its tokens to the shards owning their experts.  Placement follows
the paper's pod-aware guidance (DESIGN.md section 4):

* experts are sharded over the **data** axis (expert parallelism), so the
  dispatch/return all-to-alls run entirely over intra-pod ICI;
* across **pods** experts are replicated -- the DCI carries only gradient
  reduction, never token traffic;
* each expert's FFN dim is sharded over **model** (TP within the expert).

Dispatch is capacity-based (tokens beyond ``capacity_factor`` per
(src shard, dst shard) slot are dropped, standard GShard/Switch practice) and
runs inside ``shard_map`` so the all-to-all is explicit -- the dry-run HLO
shows it, and the hierarchical variant can replace it on multi-pod meshes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm.topology import WORLD_AXES, PodTopology
from repro.configs.base import MoEConfig
from repro.models.layers import MLP
from repro.models.moe_dispatch import MoEDispatcher
from repro.models.sharding import ParamSpec


@dataclasses.dataclass(frozen=True)
class MoELayer:
    d_model: int
    cfg: MoEConfig
    act: str = "silu"
    #: expert-parallel mesh axis (or tuple of axes, e.g. ``("pod", "local")``
    #: to run dispatch over the full exchange mesh)
    ep_axis: Union[str, Tuple[str, ...]] = "data"
    #: "all_to_all" (flat ``jax.lax.all_to_all``, the parity baseline) or
    #: "exchange" (node-aware :class:`~repro.comm.IrregularExchange` hops,
    #: planned per measured routing pattern -- see repro.models.moe_dispatch)
    dispatch: str = "all_to_all"
    #: exchange strategy: "auto" (advisor-picked from the measured routing
    #: histogram) or one of repro.comm.STRATEGY_NAMES
    strategy: str = "auto"
    #: inter-pod wire codec for the exchange path ("none" = full precision)
    wire: str = "none"
    #: slot granularity for routing-count bucketing (plan-cache stability)
    route_quantum: int = 8
    #: lazily-created per-layer dispatcher; not part of identity
    dispatcher: Optional[MoEDispatcher] = dataclasses.field(
        default=None, compare=False
    )

    def __post_init__(self) -> None:
        if self.dispatch not in ("all_to_all", "exchange"):
            raise ValueError(
                f"dispatch must be 'all_to_all' or 'exchange', got {self.dispatch!r}"
            )
        if self.dispatch == "exchange" and self.ep_axis == "data":
            # exchange dispatch runs over the ("pod", "local") exchange mesh
            object.__setattr__(self, "ep_axis", WORLD_AXES)

    def params(self) -> dict:
        E, M, F = self.cfg.n_experts, self.d_model, self.cfg.d_ff_expert
        p = {
            "router": ParamSpec((M, E), ("fsdp", None)),
            "w_in": ParamSpec((E, M, F), ("experts", None, "mlp")),
            "w_gate": ParamSpec((E, M, F), ("experts", None, "mlp")),
            "w_out": ParamSpec((E, F, M), ("experts", "mlp", None)),
        }
        if self.cfg.n_shared:
            shared = MLP(self.d_model, self.cfg.d_ff_expert * self.cfg.n_shared, self.act)
            p["shared"] = shared.params()
        return p

    # ------------------------------------------------------------------
    def _ep_axes(self) -> Tuple[str, ...]:
        return self.ep_axis if isinstance(self.ep_axis, tuple) else (self.ep_axis,)

    def _ep_size(self, mesh) -> int:
        """Expert-parallel degree; 1 when any ep axis is absent."""
        axes = self._ep_axes()
        if mesh is None or any(a not in mesh.axis_names for a in axes):
            return 1
        return math.prod(mesh.shape[a] for a in axes)

    def __call__(self, params, x: jnp.ndarray, mesh=None) -> jnp.ndarray:
        """x: [B, S, M].  Routed experts + optional shared experts."""
        cfg = self.cfg
        B, S, M = x.shape
        logits = jnp.einsum("bsm,me->bse", x, params["router"].astype(x.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, cfg.top_k)  # [B,S,k]
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        if self._ep_size(mesh) > 1:
            if self.dispatch == "exchange":
                routed = self._dispatch_exchange(params, x, top_p, top_e, mesh)
            else:
                routed = self._dispatch_shard_map(params, x, top_p, top_e, mesh)
        else:
            routed = self._dispatch_local(params, x, top_p, top_e)

        if cfg.n_shared:
            shared = MLP(self.d_model, cfg.d_ff_expert * cfg.n_shared, self.act)
            routed = routed + shared(params["shared"], x)
        return routed

    # ------------------------------------------------------------------
    def _expert_ffn(self, w_in, w_gate, w_out, xe: jnp.ndarray) -> jnp.ndarray:
        """Batched per-expert FFN. xe: [E, C, M] -> [E, C, M]."""
        h = jnp.einsum("ecm,emf->ecf", xe, w_in.astype(xe.dtype))
        g = jnp.einsum("ecm,emf->ecf", xe, w_gate.astype(xe.dtype))
        h = jax.nn.silu(g) * h
        return jnp.einsum("ecf,efm->ecm", h, w_out.astype(xe.dtype))

    @staticmethod
    def _fill_capacity(eid: jnp.ndarray, n_bins: int, cap: int):
        """Position of each assignment within its bin; >= cap means dropped.

        eid: [T] bin ids. Returns (pos_in_bin [T], keep mask [T]).

        Sort-based: a stable argsort groups each bin's assignments in
        original order, the position within the run is ``index - run start``
        (a ``cummax`` over run-start indices), and a 1-D inverse scatter
        restores token order.  O(T log T) time and O(T) memory -- the
        previous one-hot cumsum materialized a ``[T, n_bins]`` int32 buffer,
        O(T*E) at serving batch sizes -- and bitwise-equal to it, since the
        stable sort preserves the arrival order the cumsum counted.
        """
        t = eid.shape[0]
        order = jnp.argsort(eid, stable=True)
        idx = jnp.arange(t, dtype=jnp.int32)
        sorted_eid = eid[order]
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), sorted_eid[1:] != sorted_eid[:-1]]
        )
        start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=0)
        pos = jnp.zeros((t,), jnp.int32).at[order].set(idx - start)
        return pos, pos < cap

    # -- single-device / replicated fallback ----------------------------
    def _dispatch_local(self, params, x, top_p, top_e) -> jnp.ndarray:
        cfg = self.cfg
        B, S, M = x.shape
        T = B * S * cfg.top_k
        xt = jnp.repeat(x.reshape(B * S, M), cfg.top_k, axis=0)  # [T, M]
        eid = top_e.reshape(T)
        w = top_p.reshape(T).astype(x.dtype)
        cap = max(int(T / cfg.n_experts * cfg.capacity_factor), 1)
        pos, keep = self._fill_capacity(eid, cfg.n_experts, cap)
        slot = jnp.where(keep, eid * cap + pos, cfg.n_experts * cap)  # drop slot
        buf = jnp.zeros((cfg.n_experts * cap + 1, M), x.dtype).at[slot].set(xt)
        ye = self._expert_ffn(
            params["w_in"], params["w_gate"], params["w_out"],
            buf[:-1].reshape(cfg.n_experts, cap, M),
        ).reshape(cfg.n_experts * cap, M)
        yt = jnp.concatenate([ye, jnp.zeros((1, M), x.dtype)])[slot] * w[:, None]
        return yt.reshape(B * S, cfg.top_k, M).sum(1).reshape(B, S, M)

    # -- expert-parallel all-to-all over the data axis -------------------
    def _dispatch_shard_map(self, params, x, top_p, top_e, mesh) -> jnp.ndarray:
        cfg = self.cfg
        B, S, M = x.shape
        ep = self.ep_axis  # a mesh axis name, or a tuple of them
        nd = self._ep_size(mesh)
        if cfg.n_experts % nd:
            # Silently falling back to the replicated local path here would
            # quietly drop expert parallelism on a sharded model.
            raise ValueError(
                f"n_experts={cfg.n_experts} is not divisible by the "
                f"expert-parallel degree {nd} (mesh axis {ep!r}); choose "
                f"n_experts as a multiple of {nd}, or drop ep_axis from the "
                "mesh to run the replicated local path"
            )
        e_local = cfg.n_experts // nd
        if isinstance(ep, tuple):
            batch_axes = ep  # tokens sharded over the full exchange mesh
        else:
            batch_axes = tuple(a for a in ("pod", ep) if a in mesh.axis_names)

        def body(xl, pl, el, w_in, w_gate, w_out):
            # xl: [b, S, M] local batch; experts local: [e_local, M, F_shard].
            #
            # (A bf16 pin of this whole path was tried and refuted in
            # EXPERIMENTS.md §Perf iter 3: the f32 buffers come from XLA's
            # scatter-add backward, not from a castable leaf here.)
            in_dtype = xl.dtype
            #
            # Routing is GATHER-based: the only scatters are 1-D int32
            # inverse-permutation builds.  A 2-D `.at[slot].set(tokens)`
            # scatter materializes several full-width [slots, M] index/temp
            # buffers (measured: ~12 x 4 GiB per layer on deepseek-v2-lite,
            # dominating the memory roofline -- EXPERIMENTS.md §Perf iter 2).
            b = xl.shape[0]
            t = b * S * cfg.top_k
            xt = jnp.repeat(xl.reshape(b * S, M), cfg.top_k, axis=0)
            eid = el.reshape(t)
            w = pl.reshape(t).astype(xl.dtype)
            dst = eid // e_local  # destination data-shard
            # capacity per (src shard -> dst shard) slot; floor of 8 keeps
            # decode-time (tiny t) routing essentially drop-free
            cap = max(int(t / nd * cfg.capacity_factor), 8)
            pos, keep = self._fill_capacity(dst, nd, cap)
            slot = jnp.where(keep, dst * cap + pos, nd * cap)
            # inverse permutation: which token fills each send slot (1-D)
            inv = jnp.full((nd * cap + 1,), t, jnp.int32).at[slot].set(
                jnp.arange(t, dtype=jnp.int32)
            )[:-1]
            xt_pad = jnp.concatenate([xt, jnp.zeros((1, M), xl.dtype)])
            send = xt_pad[inv]  # [nd*cap, M] gather, no wide scatter
            send_e = jnp.concatenate([eid % e_local, jnp.full((1,), e_local, jnp.int32)])[inv]
            # all-to-all over the EP axis (intra-pod ICI by construction)
            recv = jax.lax.all_to_all(
                send.reshape(nd, cap, M), ep, 0, 0, tiled=True
            ).reshape(nd * cap, M)
            recv_e = jax.lax.all_to_all(
                send_e.reshape(nd, cap), ep, 0, 0, tiled=True
            ).reshape(nd * cap)
            # bin received tokens into local experts (second capacity stage)
            cap2 = max(int(nd * cap / e_local), 1)
            bin_id = jnp.minimum(recv_e, e_local)  # dead slots -> drop bin
            pos2, keep2 = self._fill_capacity(bin_id, e_local + 1, cap2)
            keep2 &= recv_e < e_local
            slot2 = jnp.where(keep2, bin_id * cap2 + pos2, e_local * cap2)
            inv2 = jnp.full((e_local * cap2 + 1,), nd * cap, jnp.int32).at[slot2].set(
                jnp.arange(nd * cap, dtype=jnp.int32)
            )[:-1]
            recv_pad = jnp.concatenate([recv, jnp.zeros((1, M), xl.dtype)])
            buf = recv_pad[inv2]
            ye = self._expert_ffn(
                w_in, w_gate, w_out, buf.reshape(e_local, cap2, M)
            ).reshape(e_local * cap2, M)
            # NOTE: with F sharded over "model", ye is a partial sum.  The
            # psum is deferred to the *combined* [b, S, M] output (7.5x fewer
            # bytes than psumming the dispatch-width buffer); every routing
            # op in between is linear, so the result is identical.
            back = jnp.concatenate([ye, jnp.zeros((1, M), ye.dtype)])[slot2]
            ret = jax.lax.all_to_all(
                back.reshape(nd, cap, M), ep, 0, 0, tiled=True
            ).reshape(nd * cap, M)
            yt = jnp.concatenate([ret, jnp.zeros((1, M), ret.dtype)])[slot]
            yt = yt * w[:, None]
            out = yt.reshape(b * S, cfg.top_k, M).sum(1).reshape(b, S, M)
            if "model" in mesh.axis_names and mesh.shape["model"] > 1:
                out = jax.lax.psum(out, "model")
            return out.astype(in_dtype)

        x_spec = P(batch_axes or None, None, None)
        r_spec = P(batch_axes or None, None, None)
        w_spec = P(ep, None, "model" if "model" in mesh.axis_names else None)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(x_spec, r_spec, r_spec, w_spec, w_spec,
                      P(ep, "model" if "model" in mesh.axis_names else None, None)),
            out_specs=x_spec,
            check_vma=False,
        )(x, top_p, top_e, params["w_in"], params["w_gate"], params["w_out"])

    # -- node-aware exchange dispatch over the ("pod", "local") mesh -----
    def _get_dispatcher(self, mesh) -> MoEDispatcher:
        if self.dispatcher is not None:
            return self.dispatcher
        topo = PodTopology(npods=mesh.shape["pod"], ppn=mesh.shape["local"])
        disp = MoEDispatcher(
            topo,
            strategy=self.strategy,
            wire=self.wire,
            quantum=self.route_quantum,
            mesh=mesh,
        )
        object.__setattr__(self, "dispatcher", disp)
        return disp

    def _dispatch_exchange(self, params, x, top_p, top_e, mesh) -> jnp.ndarray:
        """Capacity dispatch with both hops on the node-aware exchange stack.

        Same routing math as :meth:`_dispatch_shard_map`, restructured into
        three ``shard_map`` stages with the collectives lifted out between
        them: the flat ``jax.lax.all_to_all`` calls become planned
        :class:`~repro.comm.IrregularExchange` hops over the measured
        (bucketed) routing pattern, so skewed traffic ships only the
        occupied slot prefix per pair, the advisor can pick the strategy per
        pattern, and wire codecs apply to the DCI-crossing segments.  The
        per-pair count matrix is synced to the host each batch (a tiny
        ``[n, n]`` int32 transfer) -- that measured histogram both keys the
        bucketer and feeds the dispatcher's load histogram.

        Bitwise identical to the baseline for ``wire="none"``: kept tokens
        occupy the block prefix (at most the quantized width), and every
        slot the baseline would carry as dead (zero row / sentinel expert
        id) is reproduced by the splice maps' sentinel row.
        """
        cfg = self.cfg
        B, S, M = x.shape
        if tuple(mesh.axis_names) != WORLD_AXES:
            raise ValueError(
                f'dispatch="exchange" needs the ("pod", "local") exchange '
                f"mesh, got axes {tuple(mesh.axis_names)}"
            )
        n = mesh.shape["pod"] * mesh.shape["local"]
        if cfg.n_experts % n:
            raise ValueError(
                f"n_experts={cfg.n_experts} is not divisible by the "
                f"expert-parallel degree {n} (mesh axes {WORLD_AXES!r}); "
                f"choose n_experts as a multiple of {n}"
            )
        if B % n:
            raise ValueError(
                f'dispatch="exchange" shards the batch over all {n} ranks; '
                f"batch {B} is not divisible by {n}"
            )
        e_local = cfg.n_experts // n
        k = cfg.top_k
        b = B // n
        t = b * S * k
        cap = max(int(t / n * cfg.capacity_factor), 8)

        stages = self._exchange_stages(mesh, b, S, M, jnp.dtype(x.dtype))
        stage_send, stage_expert, stage_combine = stages

        send, send_e, slot, w, counts = stage_send(x, top_p, top_e)

        # host sync on the measured [n, n] histogram: the price of planning
        # communication for the traffic we actually have
        step = self._get_dispatcher(mesh).step(
            np.asarray(jax.device_get(counts), dtype=np.int64), cap, payload_width=M
        )
        bundle = step.bundle
        ex_d, ex_r = step.exchange_dispatch, step.exchange_return

        if ex_d is not None:
            halo_x = ex_d(send)
            halo_e = ex_d(send_e)
        else:
            halo_x = jnp.zeros((n, 0, M), send.dtype)
            halo_e = jnp.zeros((n, 0), send_e.dtype)
        map_d = jnp.asarray(bundle.map_dispatch)
        map_r = jnp.asarray(bundle.map_return)

        back = stage_expert(
            send, send_e, halo_x, halo_e, map_d,
            params["w_in"], params["w_gate"], params["w_out"],
        )

        if ex_r is not None:
            halo_b = ex_r(back)
        else:
            halo_b = jnp.zeros((n, 0, M), back.dtype)

        return stage_combine(back, halo_b, map_r, slot, w)

    def _exchange_stages(self, mesh, b: int, S: int, M: int, dtype):
        """Build (once per shape signature) the three jitted shard_map
        stages of the exchange dispatch.  Re-creating the ``shard_map``
        callables per batch would re-trace every call; wrapping them in a
        memoized ``jax.jit`` makes a steady-state batch pure cache hits
        (the only re-specialization is a halo-width change on re-plan)."""
        memo = self.__dict__.get("_stage_memo")
        if memo is None:
            memo = {}
            object.__setattr__(self, "_stage_memo", memo)
        key = (mesh, b, S, M, str(dtype))
        if key in memo:
            return memo[key]

        cfg = self.cfg
        n = mesh.shape["pod"] * mesh.shape["local"]
        e_local = cfg.n_experts // n
        k = cfg.top_k
        t = b * S * k
        cap = max(int(t / n * cfg.capacity_factor), 8)

        vec = P(WORLD_AXES, None)
        mat = P(WORLD_AXES, None, None)

        def stage_send(xl, pl, el):
            xt = jnp.repeat(xl.reshape(b * S, M), k, axis=0)  # [t, M]
            eid = el.reshape(t)
            w = pl.reshape(t).astype(xl.dtype)
            dst = eid // e_local
            pos, keep = self._fill_capacity(dst, n, cap)
            slot = jnp.where(keep, dst * cap + pos, n * cap)
            inv = jnp.full((n * cap + 1,), t, jnp.int32).at[slot].set(
                jnp.arange(t, dtype=jnp.int32)
            )[:-1]
            xt_pad = jnp.concatenate([xt, jnp.zeros((1, M), xl.dtype)])
            send = xt_pad[inv]
            send_e = jnp.concatenate(
                [eid % e_local, jnp.full((1,), e_local, jnp.int32)]
            )[inv]
            counts = jnp.zeros((n,), jnp.int32).at[dst].add(1)
            return send[None], send_e[None], slot[None], w[None], counts[None]

        def stage_expert(sd, se, hx, he, mp, w_in, w_gate, w_out):
            sd, se, hx, he, mp = sd[0], se[0], hx[0], he[0], mp[0]
            # splice canonical exchange recv back into the [n*cap] layout;
            # the sentinel row reproduces the baseline's dead slots exactly
            comb_x = jnp.concatenate([sd, hx, jnp.zeros((1, M), sd.dtype)])
            comb_e = jnp.concatenate(
                [se, he, jnp.full((1,), e_local, jnp.int32)]
            )
            recv = comb_x[mp]
            recv_e = comb_e[mp]
            cap2 = max(int(n * cap / e_local), 1)
            bin_id = jnp.minimum(recv_e, e_local)
            pos2, keep2 = self._fill_capacity(bin_id, e_local + 1, cap2)
            keep2 &= recv_e < e_local
            slot2 = jnp.where(keep2, bin_id * cap2 + pos2, e_local * cap2)
            inv2 = jnp.full((e_local * cap2 + 1,), n * cap, jnp.int32).at[
                slot2
            ].set(jnp.arange(n * cap, dtype=jnp.int32))[:-1]
            recv_pad = jnp.concatenate([recv, jnp.zeros((1, M), sd.dtype)])
            buf = recv_pad[inv2]
            ye = self._expert_ffn(
                w_in, w_gate, w_out, buf.reshape(e_local, cap2, M)
            ).reshape(e_local * cap2, M)
            back = jnp.concatenate([ye, jnp.zeros((1, M), ye.dtype)])[slot2]
            return back[None]

        def stage_combine(bk, hb, mp, sl, wl):
            bk, hb, mp, sl, wl = bk[0], hb[0], mp[0], sl[0], wl[0]
            comb = jnp.concatenate([bk, hb, jnp.zeros((1, M), bk.dtype)])
            ret = comb[mp]
            yt = jnp.concatenate([ret, jnp.zeros((1, M), ret.dtype)])[sl]
            yt = yt * wl[:, None]
            out = yt.reshape(b * S, k, M).sum(1).reshape(b, S, M)
            return out.astype(dtype)

        fns = (
            jax.jit(jax.shard_map(
                stage_send,
                mesh=mesh,
                in_specs=(mat, mat, mat),
                out_specs=(mat, vec, vec, vec, vec),
                check_vma=False,
            )),
            jax.jit(jax.shard_map(
                stage_expert,
                mesh=mesh,
                in_specs=(mat, vec, mat, vec, vec, mat, mat, mat),
                out_specs=mat,
                check_vma=False,
            )),
            jax.jit(jax.shard_map(
                stage_combine,
                mesh=mesh,
                in_specs=(mat, mat, vec, vec, vec),
                out_specs=mat,
                check_vma=False,
            )),
        )
        memo[key] = fns
        return fns
