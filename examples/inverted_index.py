"""PUMA-analog Inverted Index on the OS4M MapReduce engine.

Builds a word → document-count index over the synthetic corpus, comparing
the default hash partitioner against the OS4M schedule — the engine-level
reproduction of the paper's headline benchmark (II), including the
pipelined reduce and the §4.3 network-cost model. The engine reduces per
word (``keyed_output``), so each output row is one word, not one cluster.

Run:  PYTHONPATH=src python examples/inverted_index.py
"""

import numpy as np

import jax.numpy as jnp

from repro.core.clustering import recommended_num_clusters
from repro.core.mapreduce import MapReduceConfig, MapReduceJob
from repro.data.synthetic import CorpusConfig, documents

SLOTS = 8
PAIRS_PER_SHARD = 4096

corpus = CorpusConfig(vocab=8192, zipf_alpha=1.15)
docs = documents(corpus, seed=7, start=0, count=256)

# Map phase input: (doc_id, token) pairs, sharded across Map slots.
pairs = []
for did, d in enumerate(docs):
    for tok in np.unique(d):          # II emits (word, doc) once per doc
        pairs.append((tok, did))
rng = np.random.default_rng(0)
rng.shuffle(pairs)
pairs = pairs[: min(len(pairs) // SLOTS, PAIRS_PER_SHARD) * SLOTS]
keys = np.asarray([p[0] for p in pairs], np.int32).reshape(SLOTS, -1)
vals = np.ones((SLOTS, keys.shape[1], 1), np.float32)  # count 1 per doc
valid = np.ones(keys.shape, bool)


def map_fn(shard):
    k, v, ok = shard
    return k, v, ok


n_clusters = recommended_num_clusters(SLOTS)  # §5.4: 6–16x slots
print(f"inverted index: {len(pairs)} (word, doc) pairs, {SLOTS} slots, "
      f"{n_clusters} operation clusters")
for sched in ("hash", "os4m"):
    job = MapReduceJob(map_fn, MapReduceConfig(
        num_slots=SLOTS, num_clusters=n_clusters, scheduler=sched,
        pipeline_chunks=4, keyed_output=True), backend="vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid)))
    top = np.argsort(-res.counts, kind="stable")[:5]
    docs_of = dict(zip(res.keys[top].tolist(), res.counts[top].astype(int).tolist()))
    print(f"  {sched:5s}: balance={res.schedule.balance_ratio:.3f} "
          f"rel-std={res.schedule.rel_std:.3f} "
          f"net={res.network_cost.total / 1e6:.2f} MB "
          f"{res.keys.size} words, top words (word: docs)={docs_of}")
