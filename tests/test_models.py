"""Per-arch smoke tests (reduced configs) + decode consistency.

Every assigned architecture instantiates a REDUCED config of the same family
and runs one forward/train step on CPU asserting output shapes and no NaNs;
the full configs are exercised only by the dry-run.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import LMModel

RNG = np.random.default_rng(0)


def shrink(cfg, dtype="float32"):
    kw = dict(
        n_layers=2, d_model=64, d_ff=128 if cfg.d_ff else 0, n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2), head_dim=16, vocab_size=256,
        cross_context=8 if cfg.cross_context else 0, dtype=dtype,
    )
    if cfg.moe:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_ff_expert=32, capacity_factor=8.0,
            first_dense_layers=min(cfg.moe.first_dense_layers, 1),
        )
    if cfg.mla:
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=16, rope_head_dim=8, nope_head_dim=16, v_head_dim=16
        )
        kw["head_dim"] = 24
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, state_dim=8, head_dim=8, chunk=8)
    if cfg.encoder:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=2, context=8)
    if cfg.window:
        kw["window"] = 8
    if cfg.cross_attn_every:
        kw["cross_attn_every"] = 2
    return dataclasses.replace(cfg, **kw)


def make_batch(model, cfg, B=2, S=16):
    tokens = jnp.asarray(RNG.integers(0, 256, (B, S)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    if model.ctx_len():
        batch["ctx"] = jnp.asarray(
            RNG.normal(size=(B, model.ctx_len(), cfg.d_model)), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = shrink(get_config(arch))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(model, cfg)
    logits = model.apply(params, batch["tokens"], batch.get("ctx"))
    assert logits.shape == (2, 16, model.vocab)
    assert bool(jnp.isfinite(logits).all()), "NaN/inf in logits"
    loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch))(params)
    assert np.isfinite(float(loss))
    for path, g in jax.tree.flatten_with_path(grads)[0]:
        assert bool(jnp.isfinite(g).all()), f"NaN grad at {jax.tree_util.keystr(path)}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_matches_full_forward(arch):
    cfg = shrink(get_config(arch))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S, EXTRA = 2, 12, 3
    toks = jnp.asarray(RNG.integers(0, 256, (B, S + EXTRA)), jnp.int32)
    ctx = (
        jnp.asarray(RNG.normal(size=(B, model.ctx_len(), cfg.d_model)), jnp.float32)
        if model.ctx_len()
        else None
    )
    full = model.apply(params, toks, ctx)
    last, cache = model.prefill(params, toks[:, :S], ctx)
    np.testing.assert_allclose(
        np.asarray(last[:, 0]), np.asarray(full[:, S - 1]), rtol=5e-3, atol=5e-3
    )
    # grow linear caches to S+EXTRA
    grown = model.init_cache(B, S + EXTRA, jnp.float32)

    def blend(dst, src):
        if dst.shape != src.shape:
            return dst.at[tuple(slice(0, s) for s in src.shape)].set(src.astype(dst.dtype))
        return src.astype(dst.dtype)

    cache = jax.tree.map(blend, grown, cache)
    for t in range(EXTRA):
        logits, cache = model.decode_step(params, toks[:, S + t : S + t + 1], cache, jnp.int32(S + t))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, S + t]), rtol=5e-2, atol=5e-2
        )


def test_chunked_attention_equals_dot():
    cfg = shrink(get_config("qwen3-32b"))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(2))
    toks = jnp.asarray(RNG.integers(0, 256, (2, 32)), jnp.int32)
    a = model.apply(params, toks, impl="dot")
    b = model.apply(params, toks, impl="chunked")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def test_head_padding_rules():
    from repro.models.transformer import pad_heads

    assert pad_heads(56, 8, 16) == (64, 8)  # deepseek-coder on 16-way TP
    assert pad_heads(25, 5, 16) == (32, 8)  # hymba
    assert pad_heads(20, 20, 16) == (32, 32)  # whisper (MHA)
    assert pad_heads(40, 8, 16) == (48, 8)  # llama4
    assert pad_heads(64, 8, 1) == (64, 8)  # no-op at tp=1
