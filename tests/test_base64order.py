"""Substrate tests: base64 ordering, cardinal projection, hashing, bitfield.

Mirrors the reference's pure data-structure unit tests (SURVEY.md §4:
DigestURLTest / Base64-order behavior / ConcurrentScoreMapTest style).
"""

import numpy as np
import pytest

from yacy_search_server_tpu.utils.base64order import (
    Base64Order, enhanced_coder, standard_coder, hashes_to_uint8, LONG_MAX,
)
from yacy_search_server_tpu.utils import hashes
from yacy_search_server_tpu.utils.bitfield import (
    Bitfield, FLAG_APP_DC_TITLE, FLAG_CAT_HASIMAGE,
)


class TestCodec:
    def test_encode_decode_long_roundtrip(self):
        for v in [0, 1, 63, 64, 4095, 123456789, (1 << 48) - 1]:
            enc = enhanced_coder.encode_long(v, 10)
            assert len(enc) == 10
            assert enhanced_coder.decode_long(enc) == v

    def test_encode_bytes_roundtrip(self):
        for coder in (enhanced_coder, standard_coder):
            for data in [b"", b"a", b"ab", b"abc", b"hello world!", bytes(range(256))]:
                enc = coder.encode(data)
                assert coder.decode(enc) == data

    def test_standard_matches_rfc_base64(self):
        import base64
        data = b"The quick brown fox jumps over the lazy dog"
        assert standard_coder.encode(data) == base64.b64encode(data)

    @pytest.mark.parametrize("rfc", (False, True))
    def test_encode_matches_the_per_byte_loop(self, rfc):
        """The reference's loop (Base64Order.java encode), as this module
        had it until PR 31, over every tail length and both alphabets:
        url and word hashes must not move by a bit."""
        coder = Base64Order(rfc)

        def loop(data: bytes) -> bytes:
            out, n, i = bytearray(), len(data), 0
            while i + 3 <= n:
                x = (data[i] << 16) | (data[i + 1] << 8) | data[i + 2]
                out += coder.encode_long(x, 4)
                i += 3
            if n - i == 2:
                x = (data[i] << 16) | (data[i + 1] << 8)
                out += coder.encode_long(x, 4)[:3] + (b"=" if rfc else b"")
            elif n - i == 1:
                out += coder.encode_long(data[i] << 16, 4)[:2] \
                    + (b"==" if rfc else b"")
            return bytes(out)

        rng = np.random.default_rng(31)
        for n in list(range(0, 34)) + [255, 1000]:
            for _ in range(20):
                data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                assert coder.encode(data) == loop(data), (n, data)
        assert coder.encode(b"\xff" * 16) == loop(b"\xff" * 16)

    def test_zero_is_capital_a(self):
        assert enhanced_coder.encode_long(0, 3) == b"AAA"


class TestOrdering:
    def test_compare_follows_alphabet(self):
        # alphabet order: A < Z < a < z < 0 < 9 < - < _
        assert enhanced_coder.compare(b"A", b"Z") < 0
        assert enhanced_coder.compare(b"Z", b"a") < 0
        assert enhanced_coder.compare(b"z", b"0") < 0
        assert enhanced_coder.compare(b"9", b"-") < 0
        assert enhanced_coder.compare(b"-", b"_") < 0
        assert enhanced_coder.compare(b"abc", b"abc") == 0

    def test_wellformed(self):
        assert enhanced_coder.wellformed(b"AZaz09-_")
        assert not enhanced_coder.wellformed(b"+/")  # standard-alphabet chars
        assert standard_coder.wellformed(b"+/")


class TestCardinal:
    def test_range_and_monotonicity(self):
        keys = [b"AAAAAAAAAAAA", b"ABCDEFGHIJKL", b"zzzzzzzzzzzz", b"____________"]
        cards = [enhanced_coder.cardinal(k) for k in keys]
        for c in cards:
            assert 0 <= c <= LONG_MAX
        assert cards == sorted(cards)

    def test_low_bits_set(self):
        # cardinal always ends in ...111 (<<3 | 7)
        assert enhanced_coder.cardinal(b"AAAAAAAAAAAA") & 7 == 7

    def test_short_key_padded(self):
        assert enhanced_coder.cardinal(b"B") == (1 << (6 * 9)) << 3 | 7

    def test_uncardinal_inverse(self):
        k = b"MhsnzAIVBCDE"
        c = enhanced_coder.cardinal(k)
        assert enhanced_coder.uncardinal(c) == k[:10]

    def test_bulk_matches_scalar(self):
        rng = np.random.default_rng(0)
        alpha = np.frombuffer(enhanced_coder.alpha, dtype=np.uint8)
        keys = alpha[rng.integers(0, 64, size=(100, 12))]
        bulk = enhanced_coder.cardinal_array(keys)
        for i in range(100):
            assert bulk[i] == enhanced_coder.cardinal(keys[i].tobytes())


class TestHashes:
    def test_word2hash_properties(self):
        h = hashes.word2hash("yacy")
        assert len(h) == 12
        assert enhanced_coder.wellformed(h)
        assert hashes.word2hash("YaCy") == h          # case-insensitive
        assert hashes.word2hash("other") != h

    def test_url2hash_layout(self):
        h1 = hashes.url2hash("http://example.com/a/page.html")
        h2 = hashes.url2hash("http://example.com/other/doc.html")
        h3 = hashes.url2hash("http://elsewhere.org/a/page.html")
        assert len(h1) == 12
        # same host => same global part (chars 6..11)
        assert h1[6:11] == h2[6:11]
        assert h1[6:11] != h3[6:11]
        # different url => different local part
        assert h1[:5] != h2[:5]
        assert hashes.hosthash(h1) == h1[6:12]

    def test_domlength_from_flagbyte(self):
        h = hashes.url2hash("http://ex.com/")          # dom "ex" <= 8
        assert hashes.dom_length_estimation(h) == 4
        h = hashes.url2hash("http://a-very-long-domain-name.com/")
        assert hashes.dom_length_estimation(h) == 20

    def test_normalform(self):
        assert hashes.normalform("HTTP://Example.COM:80/x") == "http://example.com/x"
        assert hashes.normalform("https://example.com:8443/x") == "https://example.com:8443/x"


class TestBitfield:
    def test_set_get_clear(self):
        b = Bitfield()
        assert not b.get(FLAG_APP_DC_TITLE)
        b.set(FLAG_APP_DC_TITLE)
        assert b.get(FLAG_APP_DC_TITLE)
        b.set(FLAG_APP_DC_TITLE, False)
        assert not b.get(FLAG_APP_DC_TITLE)

    def test_matches_constraint(self):
        b = Bitfield()
        b.set(FLAG_APP_DC_TITLE)
        b.set(FLAG_CAT_HASIMAGE)
        constraint = (1 << FLAG_APP_DC_TITLE)
        assert b.matches(constraint)
        assert not Bitfield().matches(constraint)


def test_hashes_to_uint8():
    hs = [hashes.word2hash("a"), hashes.word2hash("b")]
    arr = hashes_to_uint8(hs)
    assert arr.shape == (2, 12)
    assert arr[0].tobytes() == hs[0]
