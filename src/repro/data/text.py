"""Words of a text on the device: a tokenizer and a word hash in ``jax.numpy``.

:func:`hash_tokens` turns a shard's bytes into the engine's map contract
``(key_hash:int32, valid:bool)``, one pair slot per byte: the slot where a
word starts is valid and keyed by the word's hash. Words are split as
Java's ``StringTokenizer`` splits them by default (Hadoop's WordCount
``TokenizerMapper``): on space, ``\\t``, ``\\n``, ``\\r`` and ``\\f``; every
other byte belongs to a word. The hash is 32-bit FNV-1a over the word's
bytes, masked to 31 bits so that keys are non-negative.

A word is hashed on its first ``max_word_bytes`` bytes: two words that
share those and differ later get one key. A caller whose words may be
longer raises ``max_word_bytes`` (each byte of it is one unrolled step).
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["DELIMITERS", "FNV_OFFSET", "FNV_PRIME", "KEY_MASK", "hash_tokens"]

DELIMITERS = b" \t\n\r\f"
FNV_OFFSET = 0x811C9DC5
FNV_PRIME = 0x01000193
KEY_MASK = 0x7FFFFFFF


def _in_words(text: jnp.ndarray) -> jnp.ndarray:
    """True where a byte is not a delimiter."""
    out = jnp.ones(text.shape, bool)
    for d in DELIMITERS:
        out = out & (text != d)
    return out


def hash_tokens(text: jnp.ndarray, max_word_bytes: int = 32):
    """``(keys int32, valid bool)`` of the bytes ``text``, along its last axis.

    ``valid[..., p]`` is set where a word starts at byte ``p``;
    ``keys[..., p]`` is then the 31-bit FNV-1a hash of that word (of its
    first ``max_word_bytes`` bytes). Elsewhere ``keys`` holds the hash of
    the rest of the word (or of nothing) and is not to be read.
    """
    text = jnp.asarray(text, jnp.uint8)
    k = text.shape[-1]
    lead = text.shape[:-1]
    word = _in_words(text)
    starts = word & ~jnp.concatenate([jnp.zeros(lead + (1,), bool), word[..., :-1]], -1)
    pad = jnp.full(lead + (max_word_bytes,), DELIMITERS[0], jnp.uint8)
    padded = jnp.concatenate([text, pad], -1)
    padded_word = jnp.concatenate([word, jnp.zeros(pad.shape, bool)], -1)
    h = jnp.full(text.shape, FNV_OFFSET, jnp.uint32)
    alive = jnp.ones(text.shape, bool)
    for j in range(max_word_bytes):
        alive = alive & padded_word[..., j:j + k]
        step = (h ^ padded[..., j:j + k].astype(jnp.uint32)) * jnp.uint32(FNV_PRIME)
        h = jnp.where(alive, step, h)
    keys = (h & jnp.uint32(KEY_MASK)).astype(jnp.int32)
    return keys, starts
