"""End-to-end system behaviour: training convergence, fault tolerance,
elastic resharding, distributed SpMV, hierarchical collectives."""

import dataclasses
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig
from repro.runtime.trainer import SimulatedFailure


def tiny_cfg():
    cfg = get_config("stablelm-3b")
    return dataclasses.replace(
        cfg, n_layers=2, d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=512, dtype="float32",
    )


def test_training_loss_decreases():
    mesh = make_host_mesh(1, 1)
    t = Trainer(
        tiny_cfg(), mesh,
        TrainerConfig(steps=40, log_every=5, checkpoint_every=1000, batch=8, seq_len=64),
        AdamWConfig(peak_lr=3e-3, warmup_steps=4, total_steps=40),
    )
    out = t.run(resume=False)
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0] - 0.1, losses


def test_failure_injection_and_lossless_restart():
    """Train to 20 with a crash at 15; resume must replay 10..20 and produce
    the exact same final state as an uninterrupted run (deterministic data +
    checkpointed optimizer/step)."""
    mesh = make_host_mesh(1, 1)
    common = dict(log_every=5, checkpoint_every=10, batch=4, seq_len=32)
    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        # uninterrupted reference
        ref = Trainer(tiny_cfg(), mesh, TrainerConfig(steps=20, checkpoint_dir=d1, **common))
        ref_out = ref.run(resume=False)
        # crash at 15, restart
        t = Trainer(tiny_cfg(), mesh, TrainerConfig(steps=20, checkpoint_dir=d2,
                                                    fail_at_step=15, **common))
        with pytest.raises(SimulatedFailure):
            t.run(resume=False)
        t.ckpt.wait()
        t2 = Trainer(tiny_cfg(), mesh, TrainerConfig(steps=20, checkpoint_dir=d2, **common))
        out = t2.run(resume=True)
        for a, b in zip(jax.tree.leaves(ref_out["state"]["params"]),
                        jax.tree.leaves(out["state"]["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_elastic_resharding_across_meshes(subproc):
    """Checkpoint written on a 1x1 mesh restores and continues on 2x4."""
    subproc(
        """
import dataclasses, tempfile, os
import jax, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.runtime import Trainer, TrainerConfig

cfg = dataclasses.replace(get_config("stablelm-3b"), n_layers=2, d_model=64, d_ff=128,
                          n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=512, dtype="float32")
d = tempfile.mkdtemp()
common = dict(log_every=5, checkpoint_every=10, batch=8, seq_len=32)
t1 = Trainer(cfg, make_host_mesh(1, 1), TrainerConfig(steps=10, checkpoint_dir=d, **common))
t1.run(resume=False)
# resume on a different mesh: 2-way data x 4-way model
t2 = Trainer(cfg, make_host_mesh(2, 4), TrainerConfig(steps=20, checkpoint_dir=d, **common))
out = t2.run(resume=True)
assert out["history"][-1]["step"] == 20
assert np.isfinite(out["history"][-1]["loss"])
print("ELASTIC OK", out["history"][-1])
""",
        devices=8,
    )


@pytest.mark.slow
def test_distributed_spmv_all_strategies(subproc):
    subproc(
        """
import numpy as np
from repro.comm.topology import PodTopology
from repro.sparse import audikw_like, thermal_like, build

rng = np.random.default_rng(42)
topo = PodTopology(npods=2, ppn=4)
for gen in (lambda: audikw_like(64, rng), lambda: thermal_like(64, rng)):
    A = gen()
    v = rng.normal(size=(A.n,)).astype(np.float32)
    want = A.spmv(v)
    for strat in ("standard", "two_step", "three_step", "split", "auto"):
        sp = build(A, topo, strategy=strat, use_pallas=True)
        out = np.asarray(sp(v.reshape(topo.nranks, -1))).reshape(-1)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
print("SPMV OK")
""",
        devices=8,
    )


@pytest.mark.slow
def test_hierarchical_collectives_and_compression(subproc):
    subproc(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.comm import (psum_hierarchical, psum_flat, all_to_all_hierarchical, Compressor)

mesh = jax.make_mesh((2, 4), ("pod", "data"))
x = np.random.default_rng(0).normal(size=(8, 5, 3)).astype(np.float32)

def body(v):
    return psum_hierarchical(v, "pod", "data"), psum_flat(v, "pod", "data")
f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=(P(("pod", "data")), P(("pod", "data")))))
a, b = f(x)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

def body2(v):
    return (all_to_all_hierarchical(v, "pod", "data"),
            jax.lax.all_to_all(v, ("pod", "data"), 0, 0, tiled=True))
g = jax.jit(jax.shard_map(body2, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=(P(("pod", "data")), P(("pod", "data")))))
y, z = g(np.arange(64.0, dtype=np.float32).reshape(64, 1))
np.testing.assert_allclose(np.asarray(y), np.asarray(z))

comp = Compressor()
def body3(v, r):
    return psum_hierarchical(v, "pod", "data", comp, r)
h = jax.jit(jax.shard_map(body3, mesh=mesh,
                          in_specs=(P(("pod", "data")), P(("pod", "data"))),
                          out_specs=(P(("pod", "data")), P(("pod", "data")))))
xs = np.random.default_rng(1).normal(size=(8, 16)).astype(np.float32)
out, res = h(xs, np.zeros((8, 4), np.float32))
true = xs.sum(0)
rel = np.abs(np.asarray(out)[0] - true).max() / np.abs(true).max()
assert rel < 0.02, rel
assert np.isfinite(np.asarray(res)).all()
print("HIER OK")
""",
        devices=8,
    )


@pytest.mark.slow
def test_moe_dispatch_shard_map_matches_local(subproc):
    """Expert-parallel a2a dispatch == replicated-local dispatch when
    capacities are loose."""
    subproc(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.models.moe import MoELayer
from repro.models.sharding import init_params

mesh = jax.make_mesh((4,), ("data",))
moe = MoELayer(32, MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, capacity_factor=8.0))
p = init_params(moe.params(), jax.random.PRNGKey(0), jnp.float32)
x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 4, 32)), jnp.float32)
y_local = moe(p, x, mesh=None)
y_dist = moe(p, x, mesh=mesh)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_dist), rtol=2e-3, atol=2e-3)
print("MOE OK")
""",
        devices=4,
    )
