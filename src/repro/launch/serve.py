"""Batched serving launcher: prefill a prompt batch, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --preset tiny \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import PRESETS
from repro.models import ExpertLoadHistogram, LMModel


def routing_counts(params, cfg, tokens, nranks: int) -> np.ndarray:
    """Measured (src rank -> dst rank) routed-token counts for served tokens.

    Replays the first MoE layer's router over the embedded token ids (the
    layer-0 approximation: later layers see residual-mixed activations, but
    the first routing decision is exact) and bins the top-k assignments by
    source shard (batch rows block-sharded over ranks, matching the dispatch
    hop's token splice) and destination shard (experts block-sharded over
    ranks).  This is the traffic matrix the dispatch hop would carry -- the
    advisor's measured histogram.
    """
    if cfg.family != "moe":
        raise ValueError(f"--advise-dispatch needs a MoE arch, got {cfg.family!r}")
    emb = np.asarray(params["embed"])  # [V, M]
    router = np.asarray(params["seg_moe"]["moe"]["router"])[0]  # [M, E]
    toks2 = np.asarray(tokens)  # [B, S] (a flat [N] is treated as B=N, S=1)
    toks = toks2.reshape(-1)
    logits = emb[toks] @ router
    k = cfg.moe.top_k
    top = np.argsort(-logits, axis=-1)[:, :k]  # [N, k]
    e_per = max(cfg.moe.n_experts // nranks, 1)
    # Source shard = block-sharded owner of the token's batch ROW, the
    # np.array_split convention the dispatch hop splices by (first B % nranks
    # ranks carry one extra row).  Flat-index binning (arange(N) * nranks // N)
    # agrees only when B % nranks == 0; on ragged batches it splits a row
    # across ranks and misattributes its traffic.
    rows = toks2.shape[0] if toks2.ndim > 1 else toks.size
    sizes = np.full(nranks, rows // nranks, dtype=np.int64)
    sizes[: rows % nranks] += 1
    owner = np.repeat(np.arange(nranks), sizes)  # [rows]
    src = np.repeat(np.repeat(owner, toks.size // rows), k)
    dst = np.minimum(top.reshape(-1) // e_per, nranks - 1)
    counts = np.zeros((nranks, nranks), dtype=np.int64)
    np.add.at(counts, (src, dst), 1)
    return counts


def dispatch_advice(params, cfg, tokens, npods: int, ppn: int,
                    machine: str = "tpu_v5e_pod"):
    """Rank exchange strategies for the traffic this serving run produced.

    Returns ``(counts, advice)``: the measured ``[nranks, nranks]`` routing
    histogram and the :class:`repro.core.Advice` ranking for it, with byte
    terms scaled by ``d_model`` (each routed token ships a d_model-wide
    activation row).
    """
    nranks = npods * ppn
    counts = routing_counts(params, cfg, tokens, nranks)
    hist = ExpertLoadHistogram(nranks)
    hist.update(counts)
    advice = hist.advise(ppn=ppn, payload_width=cfg.d_model, machine=machine)
    return counts, advice


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--preset", choices=list(PRESETS), default="tiny")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--advise-dispatch", action="store_true",
                    help="after serving, rank exchange strategies for the "
                         "measured MoE routing histogram (MoE archs only)")
    ap.add_argument("--npods", type=int, default=2,
                    help="pods assumed for --advise-dispatch")
    ap.add_argument("--ppn", type=int, default=4,
                    help="chips per pod assumed for --advise-dispatch")
    ap.add_argument("--simulate-serving", type=int, default=0, metavar="N",
                    help="with --advise-dispatch: replay N concurrent dispatch "
                         "requests of the measured routing pattern through the "
                         "continuous-batching simulator (repro.serving) and "
                         "report coalesced vs sequential p50/p99/throughput")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="with --simulate-serving: re-run the simulation under "
                         "a seeded fault storm (FaultPlan(SEED)) and report the "
                         "recovery-ladder outcome: faults, recoveries, sheds, "
                         "breaker probes, deadline misses, trace hash")
    args = ap.parse_args()
    use_compile_cache()

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_host_mesh(d, m)
    cfg = PRESETS[args.preset](get_config(args.arch))
    model = LMModel(cfg, tp=m)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    ctx = (
        jnp.asarray(rng.normal(size=(args.batch, model.ctx_len(), cfg.d_model)), jnp.float32)
        if model.ctx_len()
        else None
    )
    max_len = args.prompt_len + args.gen

    t0 = time.time()
    logits, cache = model.prefill(params, prompts, ctx, mesh=mesh)
    # re-home the prefill cache into max_len-deep buffers
    full = model.init_cache(args.batch, max_len, model.dtype)

    def blend(dst, src):
        if dst.shape != src.shape:
            return dst.at[tuple(slice(0, s) for s in src.shape)].set(src.astype(dst.dtype))
        return src.astype(dst.dtype)

    cache = jax.tree.map(blend, full, cache)
    t1 = time.time()

    decode = jax.jit(model.decode_step, static_argnames=())
    token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    outs = [token]
    for t in range(args.gen - 1):
        logits, cache = decode(params, token, cache, jnp.int32(args.prompt_len + t))
        token = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
        outs.append(token)
    gen = jnp.concatenate(outs, axis=1)
    t2 = time.time()
    print(f"prefill {args.batch}x{args.prompt_len} in {t1-t0:.2f}s; "
          f"decoded {args.gen} tokens/seq in {t2-t1:.2f}s")
    print("generated:", np.asarray(gen)[:, :10])

    if args.advise_dispatch:
        served = np.concatenate([np.asarray(prompts), np.asarray(gen)], axis=1)
        counts, advice = dispatch_advice(params, cfg, served, args.npods, args.ppn)
        print(f"dispatch advice ({args.npods} pods x {args.ppn}, "
              f"{int(counts.sum())} routed tokens):")
        print(advice.table())
        if args.simulate_serving:
            from repro.serving import SimConfig, WorkloadClass, serving_report
            from repro.testing import make_trace

            cls = WorkloadClass.from_routing(
                counts, ppn=args.ppn, d_model=cfg.d_model, fp="moe"
            )
            trace = make_trace(
                0, args.simulate_serving, ["moe"], pattern="burst",
                rate=50 * args.simulate_serving, kinds={"moe": "moe"},
            )
            rep = serving_report({"moe": cls}, trace, SimConfig(max_width=8))
            co, sq = rep["coalesced"], rep["sequential"]
            print(f"serving sim ({args.simulate_serving} requests, k<=8): "
                  f"coalesced p50={co['p50_s']*1e3:.2f}ms p99={co['p99_s']*1e3:.2f}ms "
                  f"{co['throughput_rps']:.0f} rps | sequential "
                  f"{sq['throughput_rps']:.0f} rps | speedup {rep['speedup']:.2f}x")
            if args.chaos is not None:
                from repro.comm.faults import FaultPlan, FaultSpec
                from repro.serving import simulate

                plan = FaultPlan(
                    seed=args.chaos,
                    specs=(
                        FaultSpec(kind="perturb", prob=0.25, frac=0.1),
                        FaultSpec(kind="slow", prob=0.1, delay_s=2e-3),
                    ),
                )
                storm = simulate(
                    {"moe": cls}, trace,
                    SimConfig(max_width=8, chaos=plan, deadline_s=0.05),
                )
                total = storm.completed + storm.shed
                rate = storm.completed / total if total else 1.0
                print(f"chaos storm (seed {args.chaos}): "
                      f"{storm.fault_events} faults, "
                      f"{storm.recoveries} ladder recoveries, "
                      f"{storm.shed} shed, {storm.probes} probes "
                      f"({storm.probe_recoveries} closed breakers), "
                      f"{storm.deadline_misses} deadline misses | "
                      f"completion {rate:.1%} | trace {storm.trace_hash[:12]}")


if __name__ == "__main__":
    main()
