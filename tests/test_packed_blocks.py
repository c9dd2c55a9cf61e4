"""Bit-packed posting block format (ops/packed.py) — round-trip
property tests over adversarial column ranges, device-decode parity, and
the compression accounting `DeviceStore_p` reports.

The pack/unpack twins must be exact inverses for EVERY int16-compact
block (the parity of the whole compressed-residency subsystem rests on
it), and the traced device decode must agree with the host unpack bit
for bit — these are the anchors the *_bp kernel oracles build on.
"""

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.ops import packed as PK


def _roundtrip(f16, fl, dd):
    pb = PK.pack_block(f16, fl, dd)
    f2, fl2, dd2 = PK.unpack_block(pb)
    assert (f2 == f16).all()
    assert (fl2 == fl).all()
    assert (dd2 == dd).all()
    return pb


def _random_block(rng, n, lo=-32768, hi=32767):
    f16 = rng.integers(lo, hi, (n, P.NF)).astype(np.int16)
    f16[:, P.F_FLAGS] = 0          # compact blocks zero the flags column
    fl = rng.integers(0, 2 ** 30, n).astype(np.int32)
    dd = rng.integers(0, 2 ** 31 - 1, n).astype(np.int32)
    return f16, fl, dd


@pytest.mark.parametrize("n", (1, 7, 255, 4096, 32768 + 13))
def test_roundtrip_random_full_range(n):
    rng = np.random.default_rng(n)
    f16, fl, dd = _random_block(rng, n)
    _roundtrip(f16, fl, dd)


def test_roundtrip_all_equal_columns():
    """Constant columns (span 0) pack at the 1-bit floor and decode to
    the constant — the all-equal adversarial case."""
    n = 500
    f16 = np.full((n, P.NF), -123, np.int16)
    f16[:, P.F_FLAGS] = 0
    fl = np.full(n, 7, np.int32)
    dd = np.full(n, 42, np.int32)
    pb = _roundtrip(f16, fl, dd)
    assert (pb.widths == 1).all()
    assert pb.compression_ratio > 8


def test_roundtrip_negative_and_mixed_sign():
    n = 1000
    rng = np.random.default_rng(3)
    f16 = rng.integers(-32768, 0, (n, P.NF)).astype(np.int16)
    f16[:, P.F_FLAGS] = 0
    f16[:, 3] = rng.integers(-5, 6, n)       # tiny mixed-sign span
    fl = np.zeros(n, np.int32)
    dd = np.arange(n, dtype=np.int32)
    pb = _roundtrip(f16, fl, dd)
    assert pb.widths[3] <= 4                  # span 10 -> 4 bits


def test_roundtrip_full_width_flags_and_docids():
    """30-bit flag bitfields and near-INT32_MAX docids exercise the
    32-bit-width straddle paths."""
    n = 777
    rng = np.random.default_rng(5)
    f16 = np.zeros((n, P.NF), np.int16)
    fl = rng.integers(0, 2 ** 30, n).astype(np.int32)
    fl[0], fl[1] = 0, 2 ** 30 - 1
    dd = rng.integers(0, 2 ** 31 - 1, n).astype(np.int32)
    dd[0], dd[1] = 0, 2 ** 31 - 2
    _roundtrip(f16, fl, dd)


def test_widths_are_minimal():
    n = 64
    f16 = np.zeros((n, P.NF), np.int16)
    f16[:, 0] = np.arange(n)                  # span 63 -> 6 bits
    fl = np.zeros(n, np.int32)
    dd = np.arange(n, dtype=np.int32)         # span 63 -> 6 bits
    pb = PK.pack_block(f16, fl, dd)
    assert pb.widths[0] == 6
    assert pb.widths[PK.C_DOCIDS] == 6
    assert pb.widths[1] == 1                  # constant floor


def test_compression_accounting():
    n = 4096
    rng = np.random.default_rng(11)
    f16 = rng.integers(0, 256, (n, P.NF)).astype(np.int16)  # 8-bit cols
    f16[:, P.F_FLAGS] = 0
    fl = rng.integers(0, 2 ** 20, n).astype(np.int32)
    dd = np.arange(n, dtype=np.int32)
    pb = PK.pack_block(f16, fl, dd)
    assert pb.int16_bytes == n * (P.NF * 2 + 4 + 4)
    assert pb.packed_bytes == pb.words.nbytes
    # 8-bit columns against the 42-byte int16 row: well over 2x
    assert pb.compression_ratio > 2.0
    assert pb.row_bits == int(pb.widths.sum())


def test_device_decode_matches_host_unpack():
    """unpack_rows_dev (the traced decode the *_bp kernels fuse) agrees
    with unpack_block bit for bit, at arbitrary row offsets."""
    import jax.numpy as jnp
    rng = np.random.default_rng(17)
    n = 3000
    f16, fl, dd = _random_block(rng, n, lo=-2000, hi=2000)
    pb = PK.pack_block(f16, fl, dd)
    uw = PK.bitcast_words(jnp.asarray(pb.words))
    meta = jnp.asarray(pb.meta_vector())
    for row0, rows in ((0, 256), (100, 512), (n - 200, 128)):
        f, flg, d = PK.unpack_rows_dev(uw, jnp.int32(0), meta,
                                       jnp.int32(row0), rows)
        take = min(rows, n - row0)
        assert (np.asarray(f)[:take]
                == f16[row0:row0 + take].astype(np.int32)).all()
        assert (np.asarray(flg)[:take] == fl[row0:row0 + take]).all()
        assert (np.asarray(d)[:take] == dd[row0:row0 + take]).all()


def test_device_decode_nonzero_word_base():
    """Blocks live at arbitrary word offsets in the arena — the decode
    must honor wbase exactly."""
    import jax.numpy as jnp
    rng = np.random.default_rng(19)
    n = 500
    f16, fl, dd = _random_block(rng, n)
    pb = PK.pack_block(f16, fl, dd)
    pad = 37
    arena = np.concatenate([
        rng.integers(-2 ** 31, 2 ** 31 - 1, pad).astype(np.int32),
        pb.words])
    uw = PK.bitcast_words(jnp.asarray(arena))
    f, flg, d = PK.unpack_rows_dev(uw, jnp.int32(pad),
                                   jnp.asarray(pb.meta_vector()),
                                   jnp.int32(0), 256)
    assert (np.asarray(f)[:256] == f16[:256].astype(np.int32)).all()
    assert (np.asarray(flg)[:256] == fl[:256]).all()
    assert (np.asarray(d)[:256] == dd[:256]).all()


def test_oracle_matches_host_scorer():
    """bp_topk_oracle == compact-block host scoring over the unpacked
    rows (the parity anchor the *_bp kernel tests lean on)."""
    from yacy_search_server_tpu.ops.ranking import (
        RankingProfile, cardinal_from_stats_host, pack_stats_host)
    rng = np.random.default_rng(23)
    n = 2048
    f16 = rng.integers(0, 1000, (n, P.NF)).astype(np.int16)
    f16[:, P.F_FLAGS] = 0
    fl = rng.integers(0, 2 ** 20, n).astype(np.int32)
    dd = rng.integers(0, 10 ** 6, n).astype(np.int32)
    pb = PK.pack_block(f16, fl, dd)
    prof = RankingProfile()
    s, d = PK.bp_topk_oracle(pb, prof, "en", 10)
    stats = pack_stats_host(f16, fl)
    ref = cardinal_from_stats_host(f16, fl, stats, prof,
                                   P.pack_language("en"))
    order = np.argsort(-ref, kind="stable")[:10]
    assert (s == ref[order]).all()
    assert (d == dd[order]).all()


def test_every_bp_kernel_has_an_oracle_entry():
    """Mirrors the hygiene gate: the registry itself must carry a
    callable + contract line per kernel."""
    for name, (fn, why) in PK.BP_ORACLES.items():
        assert name.endswith("_bp_kernel")
        assert callable(fn)
        assert isinstance(why, str) and why
