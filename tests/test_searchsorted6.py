"""`ops/match._searchsorted6`, the v6 interval search of a dual-stack engine,
against a plain reference: the rows as 128-bit Python ints, `bisect_right`.

The function has two sizes (all pairs up to `_SS_FLAT` rows, blocks beyond) and
one contract: the count of rows <= the lane, for every lane and every
sorted table.  Held by row count (the empty table, one row, the threshold
and one past it, a count no block size divides, the two peer dimensions of
the 100k-rule dual-stack node) and by content (random rows; rows that share
their first 64 or 96 bits, so a later word decides; repeated rows; the
all-ones rows `pad_ruleset_entries` appends; a genuine all-ones last row
beside the function's own pads), with lanes below every row, on a row, on
every block's last row and the next block's first, above every row, and the
all-ones address.
"""

import bisect

import numpy as np
import pytest

import jax

from antrea_tpu.ops import match
from antrea_tpu.utils import ip as iputil

LANES = 1024
ALL_ONES = (1 << 128) - 1
FLAT = match._SS_FLAT
SIZES = [0, 1, FLAT, FLAT + 1, 5003, 11326, 15478]


def _random(rng, n):
    return [int.from_bytes(rng.bytes(16), "big") for _ in range(n)]


def _shared_prefix(bits):
    """Rows under three prefixes of `bits` bits: the words after decide."""
    def rows(rng, n):
        heads = [h >> (128 - bits) << (128 - bits) for h in _random(rng, 3)]
        return [heads[i % 3] | (v >> bits) for i, v in
                enumerate(_random(rng, n))]
    return rows


def _repeated(rng, n):
    return (_random(rng, -(-n // 3)) * 3)[:n]


def _entry_padded(rng, n):
    """A table `pad_ruleset_entries` brought to its rung of n rows."""
    own = _random(rng, n - max(1, n // 8)) if n else []
    tab = match.DimTable(
        bounds=np.zeros(0, np.int32), bounds6=_words(sorted(own)),
        inc=np.zeros((len(own) + 2, 1), np.uint32))
    return _values(match._pad_dim_table(tab, 0, n).bounds6)


def _all_ones_last(rng, n):
    return _random(rng, n - 1) + [ALL_ONES] if n else []


CONTENTS = {"random": _random, "shared_64_bits": _shared_prefix(64),
            "shared_96_bits": _shared_prefix(96), "repeated": _repeated,
            "entry_padded": _entry_padded, "all_ones_last": _all_ones_last}


def _words(values) -> np.ndarray:
    """128-bit ints -> (n, 4) per-word sign-flipped i32, as the device holds
    them."""
    u = np.array([[(v >> s) & 0xFFFFFFFF for s in (96, 64, 32, 0)]
                  for v in values], np.uint32).reshape(-1, 4)
    return iputil.flip_u32(u)


def _values(words) -> list:
    u = iputil.unflip_u32_array(words).tolist()
    return [(a << 96) | (b << 64) | (c << 32) | d for a, b, c, d in u]


def _lanes(rng, rows) -> list:
    lanes = [0, ALL_ONES]
    if rows:
        K = match._SS6_BLOCK
        edges = [i for k in range(K, len(rows), K) for i in (k - 1, k)]
        picks = rng.integers(0, len(rows), 96).tolist()
        lanes += [rows[0] - 1, rows[0], rows[-1], rows[-1] + 1]
        lanes += [rows[i] + d for i in edges + picks for d in (0, 1)]
        lanes += [rows[i] - 1 for i in picks]
    lanes = [min(max(v, 0), ALL_ONES) for v in lanes]
    assert len(lanes) <= LANES
    return lanes + _random(rng, LANES - len(lanes))


@pytest.fixture(scope="module")
def search():
    return jax.jit(match._searchsorted6)


@pytest.mark.parametrize("content", list(CONTENTS))
@pytest.mark.parametrize("n", SIZES)
def test_the_index_is_the_bisect_of_the_128_bit_rows(search, n, content):
    rng = np.random.default_rng([n, list(CONTENTS).index(content)])
    rows = sorted(CONTENTS[content](rng, n))
    assert len(rows) == n
    lanes = _lanes(rng, rows)
    got = np.asarray(search(_words(rows), _words(lanes)))
    want = [bisect.bisect_right(rows, v) for v in lanes]
    assert got.dtype == np.int32 and got.tolist() == want
    if n:  # the lanes reach both ends and, past the threshold, the blocks
        assert {0, n} <= set(want)
        assert n <= FLAT or len({w // match._SS6_BLOCK for w in want}) >= (
            len(set(rows)) // match._SS6_BLOCK)


def test_the_two_sizes_are_the_two_programs():
    """Up to `_SS_FLAT` rows the all-pairs count as it was; past it no
    (lanes, rows) operand is left in the program."""
    def shapes(n):
        jaxpr = jax.make_jaxpr(match._searchsorted6)(
            np.zeros((n, 4), np.int32), np.zeros((LANES, 4), np.int32))
        return {tuple(v.aval.shape) for eqn in jaxpr.eqns
                for v in eqn.outvars}
    assert (LANES, FLAT) in shapes(FLAT)
    blocked = shapes(FLAT + 1)
    assert (LANES, FLAT + 1) not in blocked
    assert (4, LANES, match._SS6_BLOCK) in blocked
    assert max(int(np.prod(s)) for s in blocked) <= 4 * LANES * max(
        match._SS6_BLOCK, FLAT // match._SS6_BLOCK + 1)
