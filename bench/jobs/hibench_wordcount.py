"""HiBench micro/wordcount as a MapReduce job: Hadoop's example WordCount.

HiBench prepares its input with Hadoop's ``RandomTextWriter``, whose
records are words drawn uniformly from a fixed list of 1,000 words, and
runs Hadoop's example ``WordCount``: ``TokenizerMapper`` splits each
record on ``StringTokenizer``'s delimiters and emits ``(word, 1)``;
``IntSumReducer`` is both the combiner and the reducer. The engine runs it
as such: the map is :func:`repro.data.text.hash_tokens` (one pair slot per
byte, valid where a word starts, keyed by the word's 31-bit FNV-1a hash),
the configuration sets ``combine`` (the map-side sum) and ``keyed_output``
(one output row per word).

The text is drawn on the device (``make_batch``). The word list stands in
for RandomTextWriter's, which is not in the repository: ``words`` distinct
lowercase words of ``min_word_letters``..``max_word_letters`` letters,
drawn from the run seed and re-drawn until their hashes are distinct. How
many letters each word of the list has, and how many times each word
falls in each shard, are fixed by ``sizes_seed`` (:func:`size_table`), so
every seed has the same bytes, words and distinct words a shard, hence
the same statistics and the same compiled programs; the seed picks the
strings and the order of the words. Words are separated by one space, and
records of ``min_record_words``..``max_record_words`` words end in a
newline. Each shard starts at a record boundary, and its tail, past its
last record, is spaces.

The reference and the control are plain Python and numpy over host copies
of the bytes, independent of the engine and of ``repro.data.text``: each
shard's bytes split on whitespace (``bytes.split``, which also splits on
``\\x0b``; the text holds none), every word counted, each distinct word
hashed by a plain-Python FNV-1a.
"""

from __future__ import annotations

import collections
import math

import ml_dtypes
import numpy as np

from repro.data import text as device_text

COLUMNS = ("text",)
VALUE_DIM = 1
MAX_WORD_BYTES = 32
SPACE, NEWLINE = ord(" "), ord("\n")
FNV_OFFSET, FNV_PRIME, KEY_MASK = 0x811C9DC5, 0x01000193, 0x7FFFFFFF
_DELIMITERS = np.frombuffer(b" \t\n\r\f", np.uint8)


def _layout(job: dict):
    """The letters of each word of the list, and the words a shard: from
    ``sizes_seed``, the same for every run seed. The words a shard fill it
    but for a margin of six standard deviations of their bytes."""
    if int(job["max_word_bytes"]) != MAX_WORD_BYTES:
        raise ValueError(f"the map hashes {MAX_WORD_BYTES} bytes a word, "
                         f"the configuration says {job['max_word_bytes']}")
    if int(job["max_word_letters"]) > MAX_WORD_BYTES:
        raise ValueError("words longer than max_word_bytes would share keys")
    if (int(job["max_word_letters"]) >= 32 or int(job["words"]) > 1024
            or int(job["rows_per_shard"]) > 1 << 25):
        raise ValueError("the generator packs a word's start, letters and list "
                         "index into int32: at most 31 letters, 1,024 words and "
                         "2^25 bytes a shard")
    rng = np.random.default_rng(int(job["sizes_seed"]))
    lengths = rng.integers(int(job["min_word_letters"]), int(job["max_word_letters"]) + 1,
                           int(job["words"]))
    size = lengths + 1                      # the word and its separator
    shard, mean, sd = int(job["rows_per_shard"]), size.mean(), size.std()
    root = (-6 * sd + math.sqrt(36 * sd * sd + 4 * mean * shard)) / (2 * mean)
    per_shard = int(root * root)
    if per_shard >= 1 << 21:
        raise ValueError(f"{per_shard} words a shard; the generator packs at most 2^21")
    return lengths.astype(np.int32), per_shard


def size_table(job: dict, pool_batches: int) -> dict:
    """How many times each word of the list falls in each shard of each pool
    batch, ``counts`` ``(batches, shards, words)`` int32: multinomial draws
    from ``sizes_seed``, the same for every run seed."""
    lengths, per_shard = _layout(job)
    shards = int(job["rows_per_batch"]) // int(job["rows_per_shard"])
    words = int(job["words"])
    rng = np.random.default_rng([int(job["sizes_seed"]), 1])
    counts = rng.multinomial(per_shard, np.full(words, 1.0 / words),
                             size=(pool_batches, shards)).astype(np.int32)
    used = counts @ (lengths + 1)
    if used.max() > int(job["rows_per_shard"]):
        raise ValueError(f"a shard's words take {used.max()} bytes, more than "
                         f"{job['rows_per_shard']}")
    return {"counts": counts}


def _word_letters(hot_key, lengths: np.ndarray, letters: int):
    """The word list, ``(words, letters)`` int32 codes of 'a'..'z' (a word
    uses its first ``lengths`` of them), drawn again until the words'
    hashes, as the map computes them, are distinct (traced)."""
    import jax
    import jax.numpy as jnp

    n = lengths.shape[0]
    short = jnp.arange(letters + 1) >= jnp.asarray(lengths)[:, None]

    def draw(i):
        return jax.random.randint(jax.random.fold_in(hot_key, i), (n, letters),
                                  ord("a"), ord("z") + 1, jnp.int32)

    def clash(state):
        words = jnp.pad(state[1], ((0, 0), (0, 1)))
        keys, _ = device_text.hash_tokens(jnp.where(short, SPACE, words), MAX_WORD_BYTES)
        h = jnp.sort(keys[:, 0])
        return jnp.any(h[1:] == h[:-1])

    return jax.lax.while_loop(clash, lambda s: (s[0] + 1, draw(s[0] + 1)),
                              (0, draw(0)))[1]


def _shard_text(job: dict, word_letters, lengths: np.ndarray, per_shard: int,
                counts, key):
    """One shard's bytes, ``(rows_per_shard,)`` uint8 (traced): the words of
    ``counts`` in the order ``key`` draws, records of random length."""
    import jax
    import jax.numpy as jnp

    k, letters = int(job["rows_per_shard"]), word_letters.shape[1]
    lo, hi = int(job["min_record_words"]), int(job["max_record_words"])
    k_order, k_rec = jax.random.split(key)
    slot = jnp.searchsorted(jnp.cumsum(counts), jnp.arange(per_shard), side="right")
    slot = slot[jax.random.permutation(k_order, per_shard)].astype(jnp.int32)
    length = jnp.asarray(lengths)[slot]
    start = jnp.cumsum(length + 1) - (length + 1)
    records = jax.random.randint(k_rec, (per_shard // lo + 1,), lo, hi + 1)
    last = jnp.minimum(jnp.cumsum(records) - 1, per_shard)
    newline = (jnp.zeros((per_shard + 1,), jnp.int32).at[last].set(1)[:per_shard]
               .at[per_shard - 1].set(1))
    # Each byte takes what it needs of the last word that starts at or
    # before it: marks at the starts, filled forward by a running maximum,
    # each packed so that it grows with the word (see _layout's bounds).
    at_start = jnp.full((k,), -1, jnp.int32).at[start]
    info = jax.lax.cummax(at_start.set((start * 32 + length) * 2 + newline))
    word = jax.lax.cummax(at_start.set(jnp.arange(per_shard) * 1024 + slot)) % 1024
    begin, letters_in, ends_record = info // 64, (info // 2) % 32, info % 2 == 1
    j = jnp.arange(k, dtype=jnp.int32) - begin
    letter = word_letters.reshape(-1)[word * letters + jnp.minimum(j, letters - 1)]
    sep = jnp.where(ends_record & (j == letters_in), NEWLINE, SPACE)
    byte = jnp.where(j < letters_in, letter, sep)
    used = start[-1] + length[-1] + 1
    return jnp.where(jnp.arange(k) < used, byte, SPACE).astype(jnp.uint8)


def make_batch(job: dict, key, hot_key, table, b: int) -> dict:
    """Pool batch ``b``: ``rows_per_batch`` bytes of text as one flat device
    column (traced). ``hot_key`` draws the word list (the same for every
    batch of a run), ``key`` the order of the words and the records."""
    import jax

    lengths, per_shard = _layout(job)
    shards = int(job["rows_per_batch"]) // int(job["rows_per_shard"])
    word_letters = _word_letters(hot_key, lengths, int(job["max_word_letters"]))
    text = jax.vmap(lambda c, k: _shard_text(job, word_letters, lengths, per_shard, c, k))(
        table["counts"][b], jax.random.split(key, shards))
    return {"text": text.reshape(-1)}


def map_fn(shard):
    """The job's Map, run by the engine on the device: ``(word hash, (1,) one,
    word start)`` at every byte, as ``TokenizerMapper`` emits ``(word, 1)``."""
    import jax.numpy as jnp

    keys, starts = device_text.hash_tokens(shard["text"], MAX_WORD_BYTES)
    return keys, jnp.ones(keys.shape + (1,), jnp.float32), starts


def valid(batch: dict) -> np.ndarray:
    """Where a word starts, shard-major like the batch: the map's pairs."""
    word = ~np.isin(batch["text"], _DELIMITERS)
    before = np.zeros_like(word)
    before[..., 1:] = word[..., :-1]
    return word & ~before


def group_ids(batch: dict) -> np.ndarray:
    """The key of every pair slot: the word's hash where a word starts,
    0 at the other slots, which are no pairs (numpy, FNV-1a)."""
    text = batch["text"]
    starts = valid(batch)
    padded = np.full(text.shape[:-1] + (text.shape[-1] + MAX_WORD_BYTES,), SPACE, np.uint8)
    padded[..., :text.shape[-1]] = text
    wide = np.zeros(padded.shape, bool)
    wide[..., :text.shape[-1]] = starts
    flat = padded.reshape(-1).astype(np.uint64)
    word = ~np.isin(padded.reshape(-1), _DELIMITERS)
    at = np.flatnonzero(wide.reshape(-1))
    h = np.full(at.size, FNV_OFFSET, np.uint64)
    alive = np.ones(at.size, bool)
    for j in range(MAX_WORD_BYTES):
        alive &= word[at + j]
        if not alive.any():
            break
        h = np.where(alive, ((h ^ flat[at + j]) * FNV_PRIME) & 0xFFFFFFFF, h)
    out = np.zeros(text.shape, np.int64)
    out[starts] = (h & KEY_MASK).astype(np.int64)
    return out


def fnv1a31(word: bytes) -> int:
    """32-bit FNV-1a of ``word``, masked to 31 bits (plain Python)."""
    h = FNV_OFFSET
    for byte in word:
        h = ((h ^ byte) * FNV_PRIME) & 0xFFFFFFFF
    return h & KEY_MASK


def _word_counts(batch: dict) -> collections.Counter:
    counts = collections.Counter()
    for shard in np.asarray(batch["text"], np.uint8).reshape(-1, batch["text"].shape[-1]):
        counts.update(shard.tobytes().split())
    return counts


def reference(batch: dict, num_groups: int):
    """Plain WordCount of the batch, keyed by each word's hash: ``(keys (R,),
    values (R, 1), counts (R,))`` in float64, one row per distinct word."""
    counts = _word_counts(batch)
    keys = np.array([fnv1a31(w) for w in counts], np.int64)
    n = np.array(list(counts.values()), np.float64)
    return keys, n[:, None], n


def control(batch: dict, num_groups: int):
    """The reference with each word's count summed in bfloat16, one 1 at a
    time as an accumulator of that type would (it stops at 256): the
    mildest bfloat16 path a change could take, so the check has to reject
    it. The pair counts stay exact."""
    keys, _, n = reference(batch, num_groups)
    ones = np.ones(int(n.max()), ml_dtypes.bfloat16)
    running = np.cumsum(ones, dtype=ml_dtypes.bfloat16).astype(np.float32)
    return keys, running[n.astype(np.int64) - 1][:, None], n.astype(np.float32)


def outputs(result, num_groups: int):
    """The engine's per-word table: ``(keys, values, counts)``."""
    return result.keys, result.values, result.counts
