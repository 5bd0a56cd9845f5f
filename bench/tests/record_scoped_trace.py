#!/usr/bin/env python3
"""Record ``bench/tests/data/v5e_scoped.xplane.pb``: the program's own spans
and device scopes in a small trace of one TPU host.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>

Every shape is compiled and run once before the trace starts.  Traced, each
call under the benchmark's spans (``bench.solve``, ``bench.product``,
``bench.sync``): one fused CG on one chip over a 64 x 64 SPD stencil, three
barrier SpMVs with it, and, where the host has four chips, three barrier
SpMVs each over a 4,096-row uniform random matrix on ``PodTopology(2, 2)``
with ``three_step`` (all-to-all and permute stages) and with ``two_step``
and the int8 wire codec (the codec's encode and decode).  The Python tracer
is off, and :func:`trim` keeps only what the reductions read
(:mod:`bench.trace`, :mod:`bench.scopes`), which keeps the file small.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import scopes, trace  # noqa: E402
from bench.run import require_chips  # noqa: E402
from repro.comm.topology import PodTopology, shard_ranks  # noqa: E402
from repro.solve import fused_cg, spd_system  # noqa: E402
from repro.sparse import matrices, partition, spmv  # noqa: E402

PRODUCTS = 3
#: host events the reductions read besides the spans
HOST_EVENTS = (scopes.LAUNCH, scopes.ENQUEUE)


def trim(path: str) -> None:
    """Keep the device planes' ``XLA Ops`` and ``XLA Modules`` lines, with
    only the ``tf_op`` stat of each operation's metadata, and the host's
    spans, launches and enqueues; drop every other line and event, and the
    metadata no event names."""
    pb2 = scopes.xplane_pb2()
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            keep = [ln for ln in plane.lines
                    if ln.name in (trace.OPS_LINE, scopes.MODULES_LINE)]
            del plane.lines[:]
            plane.lines.extend(keep)
            for md in plane.event_metadata.values():
                stats = [st for st in md.stats
                         if plane.stat_metadata[st.metadata_id].name == "tf_op"]
                del md.stats[:]
                md.stats.extend(stats)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                keep = [ev for ev in line.events
                        if plane.event_metadata[ev.metadata_id].name.startswith(
                            scopes.SPAN_PREFIXES)
                        or plane.event_metadata[ev.metadata_id].name in HOST_EVENTS]
                del line.events[:]
                line.events.extend(keep)
        used = {ev.metadata_id for line in plane.lines for ev in line.events}
        for mid in [m for m in plane.event_metadata if m not in used]:
            del plane.event_metadata[mid]
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def main(out: str) -> None:
    devices = require_chips(1)
    stencil = spd_system(matrices.thermal_like(4096, np.random.default_rng(0)))
    one = spmv.DistributedSpMV(partition.partition_csr(stencil, PodTopology(1, 1)),
                               strategy="standard")
    b = np.random.default_rng(1).standard_normal((1, stencil.n)).astype(np.float32)
    ops = [one]
    if len(devices) >= 4:
        graph = matrices.random_block(4096, 16 / 4096, np.random.default_rng(2))
        part = partition.partition_csr(graph, PodTopology(2, 2))
        ops += [spmv.DistributedSpMV(part, strategy="three_step"),
                spmv.DistributedSpMV(part, strategy="two_step", wire="int8")]
    inputs = [shard_ranks(np.ones((op.topo.nranks, op.rows_per_rank), np.float32), op.mesh)
              for op in ops]
    fused_cg(one, b, tol=1e-6, maxiter=100)
    for op, v in zip(ops, inputs):
        op(v).block_until_ready()

    spans = trace.Spans(traced=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="scoped-trace-") as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            with spans("bench.solve"):
                res = fused_cg(one, b, tol=1e-6, maxiter=100)
            for op, v in zip(ops, inputs):
                for _ in range(PRODUCTS):
                    with spans("bench.product"):
                        w = op(v)
                    with spans("bench.sync"):
                        w.block_until_ready()
        finally:
            jax.profiler.stop_trace()
        shutil.copy(trace.find_xspace(log_dir), out)
    trim(out)
    print(f"{out}: {Path(out).stat().st_size} bytes, CG {res.iterations} iterations, "
          f"{len(ops)} operators on {len(devices)} chips", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
