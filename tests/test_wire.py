"""Wire codec tests: round-trip bounds, codec="none" bitwise identity,
byte accounting, and device-executor parity (8-device subprocess).

The acceptance property of the wire layer (ISSUE 5): ``codec="none"`` is
bitwise identical to the codec-free executor for all 4 strategies x
barrier/overlap; lossy codecs deliver inter-pod halo values within their
pinned per-element error bounds while every on-pod value stays bit-exact;
and the reported ``wire_bytes`` show the inter-pod byte reduction.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import wire
from repro.comm.exchange import (
    ExchangePattern,
    Need,
    execute_numpy,
    plan,
    random_pattern,
    split_phase,
)
from repro.comm.fusion import fuse
from repro.comm.topology import PodTopology

STRATEGIES = ("standard", "two_step", "three_step", "split")
LOSSY = ("bf16", "f16", "int8")


def _pattern(seed=0, npods=2, ppn=4, local_size=6):
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=npods, ppn=ppn)
    return topo, random_pattern(rng, topo, local_size, p_connect=0.6, max_elems=4)


# ---------------------------------------------------------------------------
# Codec round-trip properties (numpy reference implementation)
# ---------------------------------------------------------------------------


def test_roundtrip_exact_for_representable_values():
    """bf16/f16 wires are lossless for values their mantissa can hold."""
    exact = np.float32([0.0, 1.0, -1.0, 1.5, 0.25, -2.75, 128.0, 3.0e-3 * 0])
    np.testing.assert_array_equal(wire.roundtrip_np(exact, "bf16", 1), exact)
    np.testing.assert_array_equal(wire.roundtrip_np(exact, "f16", 1), exact)
    # int8 is exact for 0 and +/- the block max
    blocks = np.float32([[127.0, -127.0, 0.0]])
    np.testing.assert_array_equal(wire.roundtrip_np(blocks, "int8", 1), blocks)


@given(seed=st.integers(0, 200), codec=st.sampled_from(LOSSY))
@settings(max_examples=40, deadline=None)
def test_roundtrip_bounded_relative_error(seed, codec):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(5, 17)) * 10.0 ** rng.integers(-3, 4)).astype(np.float32)
    rt = wire.roundtrip_np(x, codec, block_ndim=1)
    bound = wire.REL_ERROR_BOUND[codec]
    floor = wire.ABS_ERROR_FLOOR[codec]
    if codec == "int8":
        # per-block bound relative to the block's max magnitude
        amax = np.abs(x).max(axis=1, keepdims=True)
        assert (np.abs(rt - x) <= bound * amax * (1 + 1e-6)).all()
    else:
        assert (np.abs(rt - x) <= bound * np.abs(x) * (1 + 1e-6) + floor).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("codec", wire.WIRE_CODECS)
def test_roundtrip_preserves_dtype(dtype, codec):
    """Payload dtype survives every codec, including bf16 payloads."""
    import ml_dtypes

    dt = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    x = np.linspace(-1, 1, 16).astype(dt)
    rt = wire.roundtrip_np(x, codec, 1)
    assert rt.dtype == dt, f"{codec} upcast {dt} -> {rt.dtype}"


def test_narrow_payloads_pass_through_untouched():
    """A codec never widens and never re-encodes an already-narrow payload:
    a bf16 payload on a bf16 wire (or f16 on f16) is the identity."""
    import ml_dtypes

    xb = np.linspace(-3, 3, 16).astype(ml_dtypes.bfloat16)
    assert wire.roundtrip_np(xb, "bf16", 1) is xb
    xh = np.linspace(-3, 3, 16).astype(np.float16)
    assert wire.roundtrip_np(xh, "f16", 1) is xh
    assert wire.roundtrip_np(xh, "bf16", 1) is xh  # equal width: no win
    xi = np.arange(8, dtype=np.int32)
    assert wire.roundtrip_np(xi, "int8", 1) is xi  # non-float: never encoded
    assert not wire.applies("bf16", np.float16)
    assert wire.applies("int8", np.float16)


def test_bf16_payload_is_floating_for_the_int8_wire():
    """ml_dtypes.bfloat16 has numpy kind 'V', not 'f' -- the codec layer
    must still recognize it as a floating payload so the int8 wire really
    quantizes it (the byte accounting already promises the reduction)."""
    import ml_dtypes

    assert wire.applies("int8", ml_dtypes.bfloat16)
    x = np.array([1.0, 0.004], ml_dtypes.bfloat16)
    rt = wire.roundtrip_np(x, "int8", 1)
    assert rt.dtype == x.dtype
    # actually quantized: 0.004 lands on the nearest 1/127 step
    assert float(rt[1]) != float(x[1])
    assert abs(float(rt[1]) - float(x[1])) <= wire.REL_ERROR_BOUND["int8"] * 1.01


def test_cast_codecs_saturate_instead_of_overflowing():
    """Finite payload values above the wire type's max must saturate to it,
    never become infinities on the wire (bf16's window is narrow --
    ~3.39e38..f32 max -- but a diverging solve lands in it)."""
    import ml_dtypes

    big = np.float32([3.402e38, -3.402e38, 1.0e5, 1.0])
    for codec, wdt in (("bf16", ml_dtypes.bfloat16), ("f16", np.float16)):
        rt = wire.roundtrip_np(big, codec, 1)
        assert np.isfinite(rt).all(), (codec, rt)
        fmax = wire.ml_finfo_max(wdt)
        assert float(np.abs(rt).max()) <= fmax


def test_cast_codecs_propagate_true_nonfinite():
    """Saturation is for *finite* overflow only: a genuine inf/nan payload
    (a diverging solve) must cross the wire non-finite so downstream
    ``isfinite`` guards still fire -- bf16/f16 both represent inf/nan."""
    x = np.float32([np.inf, -np.inf, np.nan, 1.0, 3.402e38])
    for codec in ("bf16", "f16"):
        rt = wire.roundtrip_np(x, codec, 1)
        assert np.isposinf(rt[0]) and np.isneginf(rt[1]) and np.isnan(rt[2]), (codec, rt)
        assert rt[3] == 1.0
        # the finite out-of-range magnitude still saturates, never overflows
        assert np.isfinite(rt[4]), (codec, rt)


def test_int8_nonfinite_never_poisons_the_block():
    """One inf/nan in a wire block decodes to nan (the reserved
    INT8_NONFINITE code; int8 cannot carry inf) while every finite
    neighbor keeps the pinned bound against the block's *finite* max."""
    x = np.float32([[np.inf, 1.0, 2.0], [np.nan, 0.5, -np.inf]])
    rt = wire.roundtrip_np(x, "int8", 1)
    nonfinite = ~np.isfinite(x)
    assert np.isnan(rt[nonfinite]).all(), rt
    bound = wire.REL_ERROR_BOUND["int8"]
    finite_amax = np.max(np.where(nonfinite, 0.0, np.abs(x)), axis=1, keepdims=True)
    err = np.abs(rt - x)[~nonfinite]
    assert (err <= bound * np.broadcast_to(finite_amax, x.shape)[~nonfinite] * (1 + 1e-6)).all()
    # an all-non-finite block is all nan, not an error
    assert np.isnan(wire.roundtrip_np(np.float32([[np.nan, np.inf]]), "int8", 1)).all()


def test_device_encode_decode_matches_oracle_on_nonfinite():
    """The executor's jnp encode/decode pair is bit-identical to the numpy
    oracle for payloads containing inf/nan (the lockstep the 8-device
    parity test relies on, checked here without devices)."""
    import jax.numpy as jnp

    from repro.comm import strategies as S

    x = np.float32(
        [[np.inf, 1.0, -2.0], [np.nan, 0.5, -np.inf], [1.0, 2.0, 3.0], [0.0, 0.0, 0.0]]
    )
    for codec in LOSSY:
        payload, aux = S._encode_blocks(jnp.asarray(x), codec)
        dec = np.asarray(S._decode_blocks(payload, aux, jnp.float32))
        np.testing.assert_array_equal(dec, wire.roundtrip_np(x, codec, block_ndim=1))


def test_int8_zero_blocks_stay_zero():
    """All-PAD / all-zero wire blocks must decode to exact zeros (the
    executor's PAD handling relies on it)."""
    z = np.zeros((3, 9), np.float32)
    np.testing.assert_array_equal(wire.roundtrip_np(z, "int8", 1), z)


def test_unknown_codec_rejected():
    with pytest.raises(ValueError):
        wire.check_codec("zstd")
    with pytest.raises(ValueError):
        execute_numpy(plan("standard", _pattern()[1]), np.zeros((8, 6), np.float32), wire="zstd")


def test_spmv_unknown_strategy_with_auto_wire_raises_value_error():
    """A fixed-but-unknown strategy plus wire="auto" must fail with the
    naming ValueError, not a bare StopIteration from the ranking lookup."""
    from repro.sparse.matrices import thermal_like
    from repro.sparse.partition import partition_csr
    from repro.sparse.spmv import DistributedSpMV

    topo = PodTopology(npods=2, ppn=4)
    part = partition_csr(thermal_like(64, np.random.default_rng(0)), topo)
    with pytest.raises(ValueError, match="unknown strategy"):
        DistributedSpMV(part, strategy="two_step_1", wire="auto")


# ---------------------------------------------------------------------------
# Numpy executor: none is bitwise, lossy codecs are bounded, on-pod exact
# ---------------------------------------------------------------------------


@given(
    seed=st.integers(0, 300),
    strategy=st.sampled_from(STRATEGIES),
    fused=st.sampled_from([False, True]),
)
@settings(max_examples=30, deadline=None)
def test_codec_none_is_bitwise_identical(seed, strategy, fused):
    topo, pat = _pattern(seed)
    sp = plan(strategy, pat, message_cap_bytes=48)
    if fused:
        sp = fuse(sp)
    local = np.random.default_rng(seed).normal(size=(topo.nranks, 6)).astype(np.float32)
    base = execute_numpy(sp, local)
    np.testing.assert_array_equal(execute_numpy(sp, local, wire="none"), base)


@given(
    seed=st.integers(0, 300),
    strategy=st.sampled_from(STRATEGIES),
    codec=st.sampled_from(LOSSY),
)
@settings(max_examples=30, deadline=None)
def test_codec_bounded_error_and_onpod_exact(seed, strategy, codec):
    """Lossy codecs: inter-pod halo slots within the pinned bound, on-pod
    slots (deliverable without crossing DCI) bit-exact."""
    topo, pat = _pattern(seed)
    sp = fuse(plan(strategy, pat, message_cap_bytes=48))
    rng = np.random.default_rng(seed)
    local = rng.normal(size=(topo.nranks, 6)).astype(np.float32)
    ref = pat.reference(local)
    H = pat.max_recv_size()
    out = execute_numpy(sp, local, wire=codec)[:, :H]
    bound = wire.REL_ERROR_BOUND[codec]
    scale = np.abs(local).max()  # every wire block's amax is <= this
    assert (np.abs(out - ref[:, :H]) <= bound * scale * (1 + 1e-6)).all()
    # slots whose source is on the destination's own pod never cross DCI
    dec = split_phase(pat)
    onpod = dec.from_local[:, :H] & dec.valid[:, :H]
    np.testing.assert_array_equal(out[onpod], ref[:, :H][onpod])


@given(seed=st.integers(0, 200), codec=st.sampled_from(LOSSY))
@settings(max_examples=20, deadline=None)
def test_batched_payload_rides_the_codec(seed, codec):
    """[nranks, L, k] payloads go through the same wire blocks; each column
    stays within the same bound."""
    topo, pat = _pattern(seed, npods=2, ppn=2, local_size=5)
    sp = fuse(plan("two_step", pat))
    rng = np.random.default_rng(seed)
    loc3 = rng.normal(size=(topo.nranks, 5, 3)).astype(np.float32)
    ref = pat.reference(loc3)
    H = pat.max_recv_size()
    out = execute_numpy(sp, loc3, wire=codec)[:, :H]
    bound = wire.REL_ERROR_BOUND[codec] * np.abs(loc3).max()
    assert (np.abs(out - ref[:, :H]) <= bound * (1 + 1e-6)).all()


def test_empty_pattern_and_zero_inter_pod_traffic():
    """Edge cases: a pattern with no needs at all, and one whose needs are
    all on-pod (zero inter-pod traffic) -- every codec must be a no-op."""
    topo = PodTopology(npods=2, ppn=2)
    empty = ExchangePattern(topo=topo, local_size=4, needs=())
    onpod = ExchangePattern(
        topo=topo,
        local_size=4,
        needs=(Need(0, 1, (0, 2)), Need(3, 2, (1,))),
    )
    local = np.random.default_rng(0).normal(size=(topo.nranks, 4)).astype(np.float32)
    for pat in (empty, onpod):
        for strategy in STRATEGIES:
            sp = fuse(plan(strategy, pat, message_cap_bytes=16))
            base = execute_numpy(sp, local)
            for codec in wire.WIRE_CODECS:
                np.testing.assert_array_equal(
                    execute_numpy(sp, local, wire=codec), base
                )
                intra, inter = wire.scaled_wire_bytes(sp, codec)
                if pat is onpod:
                    assert intra == sp.wire_intra_pod_bytes


# ---------------------------------------------------------------------------
# Byte accounting
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 200), strategy=st.sampled_from(STRATEGIES))
@settings(max_examples=25, deadline=None)
def test_scaled_wire_bytes_properties(seed, strategy):
    topo, pat = _pattern(seed)
    sp = plan(strategy, pat, message_cap_bytes=48)
    # "none" reproduces the planner's accounting verbatim
    assert wire.scaled_wire_bytes(sp, "none") == (
        sp.wire_intra_pod_bytes,
        sp.wire_inter_pod_bytes,
    )
    for codec in LOSSY:
        intra, inter = wire.scaled_wire_bytes(sp, codec)
        # intra-pod hops are never touched by a wire codec
        assert intra == sp.wire_intra_pod_bytes
        assert inter <= sp.wire_inter_pod_bytes
        if sp.wire_inter_pod_bytes:
            # the acceptance target: >= 1.8x reduction for the 16-bit wires,
            # more for int8 (scale side information costs a little back)
            assert sp.wire_inter_pod_bytes / inter >= 1.8, (codec, strategy)
    # fusion must not change the accounting (wire cost is monotone)
    fused = fuse(sp)
    for codec in wire.WIRE_CODECS:
        assert wire.scaled_wire_bytes(fused, codec) == wire.scaled_wire_bytes(sp, codec)


def test_wire_itemsize_and_ratio():
    assert wire.wire_itemsize("none", 4) == 4
    assert wire.wire_itemsize("bf16", 4) == 2
    assert wire.wire_itemsize("int8", 4) == 1
    # never wider than the payload
    assert wire.wire_itemsize("bf16", 2) == 2
    assert wire.wire_itemsize("f16", 1) == 1
    assert wire.compression_ratio("int8") == 0.25


# ---------------------------------------------------------------------------
# Device executor (8-device subprocess): parity with the numpy oracle
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_device_codec_none_bitwise_and_lossy_bounded(subproc):
    """All 4 strategies x barrier/overlap: codec "none" delivers bits equal
    to the codec-free executor; lossy codecs match the numpy oracle exactly
    and the reference within the pinned bound; wire_bytes report >= 1.8x
    inter-pod reduction for bf16."""
    subproc(
        """
import numpy as np
from repro.comm import wire
from repro.comm.exchange import execute_numpy, random_pattern
from repro.comm.strategies import IrregularExchange, STRATEGY_NAMES
from repro.comm.topology import PodTopology

rng = np.random.default_rng(11)
topo = PodTopology(npods=2, ppn=4)
pat = random_pattern(rng, topo, local_size=7, p_connect=0.6, max_elems=5)
local = rng.normal(size=(topo.nranks, 7)).astype(np.float32)
ref = pat.reference(local)
H = pat.max_recv_size()
for strat in STRATEGY_NAMES:
    ex0 = IrregularExchange(pat, strat, message_cap_bytes=32)
    base = np.asarray(ex0(local))
    exn = IrregularExchange(pat, strat, message_cap_bytes=32, wire="none")
    # barrier: none is bitwise the codec-free program
    np.testing.assert_array_equal(np.asarray(exn(local)), base)
    # overlap (split-phase): none merges bit-identically too
    h = exn.start(local)
    np.testing.assert_array_equal(np.asarray(h.finish()), base)
    for codec in ("bf16", "f16", "int8"):
        exw = IrregularExchange(pat, strat, message_cap_bytes=32, wire=codec)
        out = np.asarray(exw(local))
        # device executor == numpy oracle, bit for bit, even when lossy
        np.testing.assert_array_equal(out, execute_numpy(exw.plan, local, wire=codec))
        bound = wire.REL_ERROR_BOUND[codec] * np.abs(local).max() * (1 + 1e-6)
        assert np.abs(out[:, :H] - ref[:, :H]).max() <= bound, (strat, codec)
        # split-phase with a codec stays within the same bound
        hw = exw.start(local)
        mer = np.asarray(hw.finish())
        assert np.abs(mer[:, :H] - ref[:, :H]).max() <= bound, (strat, codec)
        # on-pod phase of the split exchange is full precision
        np.testing.assert_array_equal(
            np.asarray(hw.local_halo), np.asarray(exn.start(local).local_halo)
        )
    i0, j0 = exn.wire_bytes
    ib, jb = IrregularExchange(pat, strat, message_cap_bytes=32, wire="bf16").wire_bytes
    assert ib == i0 and j0 / jb >= 1.8, (strat, (i0, j0), (ib, jb))
print("DEVICE WIRE OK")
""",
        devices=8,
    )


@pytest.mark.slow
def test_device_bf16_payload_rides_untouched(subproc):
    """A bfloat16 payload on a bf16 wire crosses DCI losslessly (the codec
    is the identity for already-narrow payloads) -- dtype preserved."""
    subproc(
        """
import numpy as np
import jax.numpy as jnp
from repro.comm.exchange import random_pattern
from repro.comm.strategies import IrregularExchange
from repro.comm.topology import PodTopology

rng = np.random.default_rng(5)
topo = PodTopology(npods=2, ppn=4)
pat = random_pattern(rng, topo, local_size=5, p_connect=0.6, max_elems=3)
local = jnp.asarray(rng.normal(size=(topo.nranks, 5)), jnp.bfloat16)
ex0 = IrregularExchange(pat, "two_step")
exw = IrregularExchange(pat, "two_step", wire="bf16")
out0 = np.asarray(ex0(local).astype(jnp.float32))
outw = exw(local)
assert outw.dtype == jnp.bfloat16, outw.dtype
np.testing.assert_array_equal(np.asarray(outw.astype(jnp.float32)), out0)
print("BF16 PAYLOAD OK")
""",
        devices=8,
    )
