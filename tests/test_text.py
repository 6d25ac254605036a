"""``repro.data.text``: the device tokenizer and word hash against plain Python.

Words are what ``bytes.split()`` gives (the text holds no ``\\x0b``, the one
byte Python splits on and ``StringTokenizer`` does not); each is keyed by a
plain-Python 32-bit FNV-1a masked to 31 bits.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.data import text

MAX = 32


def fnv1a31(word: bytes) -> int:
    h = 0x811C9DC5
    for b in word:
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def random_text(rng, size: int) -> bytes:
    """Words of 1..MAX bytes (some exactly MAX) from a few letters, so words
    repeat, between runs of 1..4 delimiters of every kind."""
    parts, n = [], 0
    while n < size:
        length = MAX if rng.random() < 0.1 else int(rng.integers(1, MAX + 1))
        word = bytes(rng.choice(list(b"abcXYZ019"), length).tolist())
        gap = bytes(rng.choice(list(text.DELIMITERS), int(rng.integers(1, 5))).tolist())
        parts += [word, gap]
        n += length + len(gap)
    return b"".join(parts)[:size]


def tokens(data: bytes):
    import jax.numpy as jnp

    keys, valid = text.hash_tokens(jnp.asarray(np.frombuffer(data, np.uint8)), MAX)
    keys, valid = np.asarray(keys), np.asarray(valid)
    return keys[valid].tolist(), np.flatnonzero(valid).tolist()


@pytest.mark.parametrize("lead", [b"", b" ", b"\t\n\r\x0c "])
@pytest.mark.parametrize("seed", [0, 1])
def test_words_and_hashes_match_plain_python(lead, seed):
    data = lead + random_text(np.random.default_rng(seed), 3000)
    keys, starts = tokens(data)
    words = data.split()
    assert keys == [fnv1a31(w) for w in words]
    assert [data[s:s + len(w)] for s, w in zip(starts, words)] == words
    assert max(len(w) for w in words) == MAX


def test_edges():
    data = b"a" * MAX + b" \x0c" + b"b" * (MAX - 1) + b"\t\tc"
    keys, starts = tokens(data)
    assert starts == [0, MAX + 2, 2 * MAX + 3]
    assert keys == [fnv1a31(w) for w in data.split()]
    assert all(0 <= k < 2 ** 31 for k in keys)
    assert tokens(b" \n\t ") == ([], [])


def test_only_the_first_max_word_bytes_are_hashed():
    long = b"x" * MAX
    keys, _ = tokens(long + b"y " + long + b"z")
    assert keys == [fnv1a31(long), fnv1a31(long)]
