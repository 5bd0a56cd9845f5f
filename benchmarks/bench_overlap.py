"""Split-phase overlap sweep: interior fraction x pods x payload width.

For a synthetic block-stencil matrix whose *boundary fraction* (rows that
read halo data) is an exact knob, this section compares the barrier pipeline
(``exchange -> compute``) against the split-phase pipeline
(``start -> interior tiles -> finish -> boundary tiles``,
``DistributedSpMV(overlap=True)``) for each (pods, interior fraction, k)
point:

* ``barrier_us`` / ``overlap_us`` -- measured wall time per step on host
  devices.  Host CPU collectives complete synchronously, so the measured
  numbers bound the overhead of the split pipeline (two phase programs plus
  the merge) rather than showing the latency hiding itself;
* ``parity=ok`` -- the overlapped result was verified bitwise-equal to the
  barrier result before timing (the acceptance property);
* ``model_barrier_s`` / ``model_overlap_s`` / ``advised`` -- the
  overlap-aware model terms (paper-style prediction:
  ``T = T_local + max(T_inter, T_interior) + T_boundary``) evaluated with a
  compute profile *at the scale of the modeled communication* (interior
  compute = best barrier comm time, split by the interior tile fraction), so
  the sweep exposes the reproduction target: the modeled overlap win grows
  with the interior fraction and vanishes at fraction 0.

``main(smoke=True)`` shrinks the sweep (one topology, 8 devices, k <= 4) so
``benchmarks/run.py --smoke`` keeps this section alive in tier-1.
"""

from __future__ import annotations

from benchmarks.common import run_with_devices

CODE = """
import time, numpy as np
from repro.comm.topology import PodTopology
from repro.core import ComputeProfile, advise
from repro.sparse import build
from repro.sparse.matrices import _from_coo

def med_us(fn, iters):
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter(); fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts)//2] * 1e6

def halo_frac_matrix(nranks, L, boundary_frac, rng):
    '''Block stencil with an exact boundary-row knob: every row has a
    diagonal + an in-block neighbour; the first round(boundary_frac * L)
    rows of each rank block also read one element of the previous block.'''
    n = nranks * L
    nb = int(round(boundary_frac * L))
    rows_l, cols_l = [], []
    for r in range(nranks):
        base = r * L
        idx = base + np.arange(L)
        rows_l += [idx, idx[:-1]]
        cols_l += [idx, idx[:-1] + 1]
        if nb and nranks > 1:
            src = (r - 1) % nranks
            rows_l.append(base + np.arange(nb))
            cols_l.append(src * L + np.arange(nb))
    rows = np.concatenate(rows_l); cols = np.concatenate(cols_l)
    return _from_coo(n, rows, cols, rng.normal(size=rows.size))

rng = np.random.default_rng(0)
L = 256 if SMOKE else 512
iters = 3 if SMOKE else 5
pods = (2,) if SMOKE else (2, 4)
fracs = (0.25, 0.75) if SMOKE else (0.125, 0.5, 0.875)
ks = (1, 4) if SMOKE else (1, 8)
for npods in pods:
    topo = PodTopology(npods=npods, ppn=4)
    for frac in fracs:
        A = halo_frac_matrix(topo.nranks, L, 1.0 - frac, rng)
        sp = build(A, topo, strategy="two_step", use_pallas=False)
        ov = build(A, topo, strategy="two_step", use_pallas=False, overlap=True)
        for k in ks:
            V = rng.normal(size=(A.n, k)).astype(np.float32)
            Vr = V.reshape(topo.nranks, L, k)
            vr = Vr[:, :, 0]
            bar = np.asarray(sp(vr) if k == 1 else sp.matmat(Vr))
            ovl = np.asarray(ov(vr) if k == 1 else ov.matmat(Vr))
            # ulp-level slack: the jnp-oracle barrier program fuses both
            # reductions under one jit (the pallas path is bitwise equal;
            # see tests/test_overlap.py)
            np.testing.assert_allclose(ovl, bar, rtol=1e-6, atol=1e-6)
            b_us = med_us(lambda: (sp(vr) if k == 1 else sp.matmat(Vr)).block_until_ready(), iters)
            o_us = med_us(lambda: (ov(vr) if k == 1 else ov.matmat(Vr)).block_until_ready(), iters)
            # the tile granularity actually executed (SpMV and SpMM share it)
            itf = ov.row_split.interior_tile_fraction
            # overlap-aware model at comm scale: interior compute sized to
            # the best barrier comm time, split by the interior tile fraction
            pat = sp.partition.pattern.to_comm_pattern()
            t_comm = advise(pat, machine="tpu_v5e_pod", payload_width=k).best.predicted_time
            prof = ComputeProfile.from_fraction(t_comm, itf)
            adv = advise(pat, machine="tpu_v5e_pod", payload_width=k, compute=prof)
            best_bar = min(r.predicted_time for r in adv.ranked if not r.overlap)
            best_ovl = min(r.predicted_time for r in adv.ranked if r.overlap)
            win = best_bar / best_ovl if best_ovl > 0 else 1.0
            print(
                f"RESULT,overlap/{npods}p/f{frac:g}/k{k},{o_us:.1f},"
                f"barrier_us={b_us:.1f} overlap_us={o_us:.1f} "
                f"int_tile_frac={itf:.3f} "
                f"model_barrier_s={best_bar:.3e} model_overlap_s={best_ovl:.3e} "
                f"model_win={win:.2f}x "
                f"advised={adv.best.key} parity=ok"
            )
"""


def main(smoke: bool = False) -> None:
    print("name,us_per_call,derived")
    devices = 8 if smoke else 16
    out = run_with_devices(f"SMOKE = {smoke!r}\n" + CODE, devices=devices)
    for line in out.splitlines():
        if line.startswith("RESULT,"):
            print(line[len("RESULT,"):])


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
