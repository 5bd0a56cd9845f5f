import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: every cell's
``train_step`` / ``prefill`` / ``serve_step`` is lowered with full-size
``ShapeDtypeStruct`` inputs (no allocation), compiled for the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh, and its
``memory_analysis()`` / ``cost_analysis()`` / collective schedule recorded to
``artifacts/dryrun/``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, shape_applicable, ARCH_IDS
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.hlo_analysis import analyze
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import LMModel, param_shardings, rules_for_mesh, spec_for
from repro.models.sharding import ParamSpec, named_sharding
from repro.optim import AdamWConfig, OptState, adamw_init
from repro.runtime.trainer import build_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# ShapeDtypeStruct builders (no allocation)
# ---------------------------------------------------------------------------


def _sds_tree(spec_tree, dtype):
    return jax.tree.map(
        lambda ps: jax.ShapeDtypeStruct(ps.shape, dtype),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def input_specs(cfg: ModelConfig, shape: ShapeConfig, model: LMModel) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["batch"] = {"tokens": tok, "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if model.ctx_len():
            out["batch"]["ctx"] = jax.ShapeDtypeStruct(
                (B, model.ctx_len(), cfg.d_model), jnp.bfloat16
            )
    elif shape.kind == "prefill":
        out["tokens"] = tok
        if model.ctx_len():
            out["ctx"] = jax.ShapeDtypeStruct((B, model.ctx_len(), cfg.d_model), jnp.bfloat16)
    else:  # decode: one new token against a seq_len-deep cache
        out["token"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        out["cache"] = jax.eval_shape(lambda: model.init_cache(B, S, jnp.bfloat16))
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def cache_shardings(cache_tree, mesh: Mesh, rules) -> Any:
    """Heuristic logical mapping for cache leaves by their key name."""

    def one(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        nd = len(leaf.shape)
        if key in ("k", "v", "cross_k", "cross_v"):
            logical = ("layers", "batch", "cache_seq", None, None)
        elif key in ("c_kv", "k_rope"):
            logical = ("layers", "batch", "cache_seq", None)
        elif key == "ssm":
            logical = ("layers", "batch", "ssm_heads", None, None)
        elif key == "conv":
            logical = ("layers", "batch", None, "ssm_heads", None)
        else:
            logical = (None,) * nd
        logical = logical[:nd] + (None,) * (nd - len(logical))
        return named_sharding(mesh, rules, logical, leaf.shape)

    flat, treedef = jax.tree.flatten_with_path(cache_tree)
    return jax.tree.unflatten(treedef, [one(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def attn_impl() -> str:
    """REPRO_ATTN_IMPL knob: "chunked" (XLA online-softmax, default) or
    "fused" (Pallas-kernel surrogate + analytic kernel terms, §Perf)."""
    return os.environ.get("REPRO_ATTN_IMPL", "chunked")


def attention_kernel_terms(cfg: ModelConfig, model: LMModel, shape: ShapeConfig) -> Dict[str, float]:
    """Analytic per-chip FLOPs/HBM-bytes of the Pallas flash kernel calls
    that the fused-attention dry-run variant replaces with a stub.

    fwd FLOPs = 4*B*H*S*Sk*D (QK^T + PV), x2.5 more for the flash backward;
    HBM bytes = Q+K+V+O traffic (x3 for fwd+bwd).  Causality halves the
    effective Sk; sliding windows clamp it.  Divided by chip count (batch,
    heads and sequence are sharded across the mesh).
    """
    from repro.models.transformer import pad_heads

    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}  # decode path uses the dot impl
    hp, kvp = pad_heads(cfg.n_heads, cfg.n_kv_heads, model.tp)
    D = cfg.resolved_head_dim
    flops = 0.0
    byts = 0.0

    def add(layers, H, KV, sq, sk, causal=True, window=None):
        nonlocal flops, byts
        eff = min(window, sk) if window else sk
        factor = 0.5 if (causal and not window) else 1.0
        flops_l = 4.0 * B * H * sq * eff * D * factor
        bytes_l = 2.0 * B * D * (sq * H + 2 * sk * KV + sq * H)  # q,k,v,o bf16
        mult_f = 3.5 if shape.kind == "train" else 1.0
        mult_b = 3.0 if shape.kind == "train" else 1.0
        flops += layers * flops_l * mult_f
        byts += layers * bytes_l * mult_b

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        if cfg.mla is None:
            n_self = cfg.n_layers if fam != "vlm" else cfg.n_layers - cfg.n_layers // cfg.cross_attn_every
            add(n_self, hp, kvp, S, S, causal=True, window=cfg.window)
        else:
            add(cfg.n_layers, hp, hp, S, S, causal=True)  # MLA expands per-head K
        if fam == "vlm":
            add(cfg.n_layers // cfg.cross_attn_every, hp, kvp, S, cfg.cross_context, causal=False)
    elif fam == "hybrid":
        add(cfg.n_layers, hp, kvp, S, S, causal=True, window=cfg.window)
    elif fam == "enc_dec":
        add(cfg.n_layers, hp, kvp, S, S, causal=True)
        add(cfg.n_layers, hp, kvp, S, cfg.encoder.context, causal=False)  # cross
        add(cfg.encoder.n_layers, hp, kvp, cfg.encoder.context, cfg.encoder.context, causal=False)
    # ssm family: no attention
    return {"flops": flops, "bytes": byts}


def lower_cell(
    arch: str, shape_name: str, mesh: Mesh
) -> Tuple[Any, LMModel]:
    """Returns (lowered computation, model) for one (arch, shape) cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules = rules_for_mesh(mesh)
    tp = mesh.shape.get("model", 1)
    model = LMModel(cfg, tp=tp)
    specs = model.param_specs()
    p_shard = param_shardings(specs, mesh, rules)
    ins = input_specs(cfg, shape, model)
    bspec = lambda shp: NamedSharding(mesh, spec_for(mesh, rules, ("batch",) + (None,) * (len(shp) - 1), shp))

    if shape.kind == "train":
        params_sds = _sds_tree(specs, jnp.float32)
        state_sds = {
            "params": params_sds,
            "opt": jax.eval_shape(adamw_init, params_sds),
        }
        step = build_train_step(
            model, mesh, AdamWConfig(), impl=attn_impl(), remat=True
        )
        batch_sh = {k: bspec(v.shape) for k, v in ins["batch"].items()}
        lowered = step.lower(state_sds, jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), ins["batch"], batch_sh))
        return lowered, model

    params_sds = _sds_tree(specs, jnp.bfloat16)
    if shape.kind == "prefill":
        def prefill_fn(params, tokens, ctx=None):
            return model.prefill(params, tokens, ctx, impl=attn_impl(), mesh=mesh)

        args = [params_sds, ins["tokens"]]
        in_sh = [p_shard, bspec(ins["tokens"].shape)]
        if "ctx" in ins:
            args.append(ins["ctx"])
            in_sh.append(bspec(ins["ctx"].shape))
        out_shape = jax.eval_shape(prefill_fn, *args)
        out_sh = (bspec(out_shape[0].shape), cache_shardings(out_shape[1], mesh, rules))
        lowered = jax.jit(prefill_fn, in_shardings=tuple(in_sh), out_shardings=out_sh).lower(*args)
        return lowered, model

    # decode
    cache_sds = ins["cache"]
    cache_sh = cache_shardings(cache_sds, mesh, rules)

    def serve_step(params, cache, token, pos):
        return model.decode_step(params, token, cache, pos, mesh=mesh)

    lowered = jax.jit(
        serve_step,
        in_shardings=(p_shard, cache_sh, bspec(ins["token"].shape), NamedSharding(mesh, P())),
        out_shardings=(bspec((ins["token"].shape[0], 1, model.vocab)), cache_sh),
        donate_argnums=(1,),
    ).lower(params_sds, cache_sds, ins["token"], ins["pos"])
    return lowered, model


# ---------------------------------------------------------------------------
# FLOPs accounting
# ---------------------------------------------------------------------------


def model_flops(cfg: ModelConfig, model: LMModel, shape: ShapeConfig) -> float:
    """6*N*D (train) / 2*N*D (inference) with N = active params (MoE-aware)."""
    specs = model.param_specs()
    total = active = 0
    for path, ps in jax.tree.flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )[0]:
        n = int(np.prod(ps.shape))
        total += n
        keys = [str(getattr(p, "key", p)) for p in path]
        if "moe" in keys and any(k in ("w_in", "w_gate", "w_out") for k in keys):
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        active += n
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens, total, active


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "skipped": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    lowered, model = lower_cell(arch, shape_name, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(mem)
    print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    hlo = analyze(compiled.as_text())
    mf, n_total, n_active = model_flops(cfg, model, shape)
    nchips = int(np.prod(list(mesh.shape.values())))
    kern_flops = kern_bytes = 0.0
    if attn_impl() == "fused":
        kt = attention_kernel_terms(cfg, model, shape)
        kern_flops = kt["flops"] / nchips
        kern_bytes = kt["bytes"] / nchips
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": nchips,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        # trip-count-weighted, per-chip (see hlo_analysis docstring); the raw
        # cost_analysis numbers (loop bodies counted once) kept for reference
        "hlo_flops_per_chip": hlo.flops + kern_flops,
        "hlo_bytes_per_chip": hlo.mem_bytes + kern_bytes,
        "hlo_flops": (hlo.flops + kern_flops) * nchips,
        "hlo_bytes": (hlo.mem_bytes + kern_bytes) * nchips,
        "analytic_kernel_flops_per_chip": kern_flops,
        "analytic_kernel_bytes_per_chip": kern_bytes,
        "knobs": {"attn_impl": attn_impl(),
                  "remat": os.environ.get("REPRO_REMAT_POLICY", "full")},
        "xla_cost_flops_raw": float(cost.get("flops", 0.0)),
        "xla_cost_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_chip": hlo.collective_bytes,
        "collective_by_kind": hlo.collective_by_kind,
        "collective_ops": hlo.collective_ops,
        "model_flops": mf,
        "params_total": n_total,
        "params_active": n_active,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACTS))
    args = ap.parse_args()
    use_compile_cache()

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch} x {shape_name} x {mesh_kind}"
                try:
                    rec = run_cell(arch, shape_name, mesh_kind, args.out)
                except Exception as e:  # noqa: BLE001 - report and continue
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                           "error": f"{type(e).__name__}: {e}"}
                    with open(os.path.join(args.out, f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
                        json.dump(rec, f, indent=1)
                if "error" in rec:
                    print(f"[FAIL] {key}: {rec['error'][:300]}")
                elif "skipped" in rec:
                    print(f"[SKIP] {key}: {rec['skipped']}")
                else:
                    print(
                        f"[ OK ] {key}: compile={rec['compile_s']}s "
                        f"flops={rec['hlo_flops']:.3e} coll={rec['collective_bytes_per_chip']:.3e}B/chip "
                        f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB"
                    )
                cells.append(rec)
    n_ok = sum(1 for c in cells if "error" not in c and "skipped" not in c)
    n_skip = sum(1 for c in cells if "skipped" in c)
    n_fail = sum(1 for c in cells if "error" in c)
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_skip} skipped (documented), {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
