"""Wire codecs + byte models for the sharded merge tree (ISSUE 9), and the
``dist.compression`` deprecation shim."""

import importlib
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.dist import wire

CODECS = sorted(wire.CODEC_DIST_BYTES)


def _rt(d, codec, lo=None, hi=None, ids=None):
    return np.asarray(wire.decode(wire.encode(jnp.asarray(d), codec, lo, hi),
                                  codec, lo, hi, ids))


def _scale(d):
    finite = np.isfinite(d)
    lo = jnp.float32(d[finite].min())
    hi = jnp.float32(d[finite].max())
    return lo, hi


@pytest.mark.parametrize("codec", CODECS)
def test_roundtrip_is_idempotent(codec):
    """decode(encode(x)) is a fixed point — the merge tree snaps values
    once and every later fold compares identical numbers."""
    rng = np.random.default_rng(0)
    d = np.abs(rng.standard_normal(512)).astype(np.float32) * 3.0
    lo, hi = _scale(d)
    once = _rt(d, codec, lo, hi)
    twice = _rt(once, codec, lo, hi)
    np.testing.assert_array_equal(once, twice)


@pytest.mark.parametrize("codec", CODECS)
def test_snap_equals_wire_roundtrip(codec):
    """``snap`` gives exactly the values a peer decodes from the wire, for
    finite, infinite and id-masked entries alike — bf16 rounding ties
    (low half-word 0x8000, to even) and negatives included."""
    rng = np.random.default_rng(2)
    d = (np.abs(rng.standard_normal(512)) * 300.0).astype(np.float32)
    bits = d.view(np.uint32)
    for i, low in enumerate((0x8000, 0x7FFF, 0x8001)):
        bits[1 + i::7] = (bits[1 + i::7] & 0xFFFF0000) | low
    d[::5] *= -1
    d[::17] = np.inf
    ids = np.where(np.arange(512) % 13 == 0, -1, 5).astype(np.int32)
    lo, hi = _scale(d)
    want = _rt(d, codec, lo, hi, ids=jnp.asarray(ids))
    got = np.asarray(wire.snap(jnp.asarray(d), codec, lo, hi,
                               jnp.asarray(ids)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("codec", CODECS)
def test_encode_is_monotone(codec):
    """d1 <= d2 implies wire(d1) <= wire(d2): quantized-domain merge order
    can only differ from exact order inside a tie bucket."""
    rng = np.random.default_rng(1)
    d = np.sort(np.abs(rng.standard_normal(1024)).astype(np.float32) * 5.0)
    lo, hi = _scale(d)
    dec = _rt(d, codec, lo, hi)
    assert (np.diff(dec) >= 0).all()


@pytest.mark.parametrize("codec", CODECS)
def test_invalid_ids_decode_to_inf(codec):
    d = np.array([0.5, 1.0, np.inf, 2.0], np.float32)
    ids = np.array([3, -1, 7, -1], np.int32)
    lo, hi = _scale(d)
    out = _rt(d, codec, lo, hi, ids=jnp.asarray(ids))
    assert np.isinf(out[[1, 2, 3]]).all()
    assert np.isfinite(out[0])


def test_u16_lossless_for_hamming_ints():
    """Popcount distances are small integers — the hamming codec is exact."""
    d = np.arange(0, 4096, dtype=np.float32)
    assert np.array_equal(_rt(d, "u16"), d)


def test_int8_overflow_saturates_to_sentinel():
    """Values past the shared hi decode to +inf, never to a small value
    that could steal a top-k slot."""
    d = np.array([0.0, 1.0, 2.0, 50.0], np.float32)
    out = _rt(d, "int8", jnp.float32(0.0), jnp.float32(2.0))
    assert np.isinf(out[3])
    assert (out[:3] <= 2.0 + 1e-6).all()


def test_entry_bytes_and_codec_table():
    assert wire.entry_bytes("f32") == 8
    assert wire.entry_bytes("bf16") == 6
    assert wire.entry_bytes("u16") == 6
    assert wire.entry_bytes("int8") == 5
    with pytest.raises(ValueError, match="unknown wire codec"):
        wire.check_codec("zstd")
    assert wire.default_codec("hamming") == "u16"
    assert wire.default_codec("euclidean") == "bf16"


def test_byte_model_hits_the_4x_gate_at_8_shards():
    """ISSUE 9 acceptance arithmetic: int8 merge wire bytes at 8 shards /
    k=10 beat the flat f32 all_gather by >= 4x."""
    flat = wire.flat_gather_wire_bytes(8, 10)
    assert flat == 8 * 10 * 8
    merged = wire.merge_wire_bytes(8, 10, codec="int8", carry=10)
    assert merged == 3 * 1 * 10 * 5 + 8
    assert flat / merged >= 4.0
    # single shard: nothing crosses the wire
    assert wire.merge_wire_bytes(1, 10) == 0
    # byte model grows with log(S), the flat baseline linearly
    assert (wire.merge_wire_bytes(64, 10, codec="bf16", carry=20)
            < wire.flat_gather_wire_bytes(64, 10))


def test_compression_shim_warns_and_reexports():
    """Satellite: the legacy ``dist.compression`` shim emits a
    DeprecationWarning but keeps the symbols intact."""
    import repro.dist.compression as shim
    from repro.dist import grad_compression

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(DeprecationWarning,
                           match="repro.dist.compression is deprecated"):
            importlib.reload(shim)
    with pytest.warns(DeprecationWarning):
        shim = importlib.reload(shim)
    assert shim.compress_gradients is grad_compression.compress_gradients
    assert shim.init_error_state is grad_compression.init_error_state
